"""What importing and starting the program does to JAX.

Importing the package must initialise no backend — on a TPU host that
would claim the chip for whichever process merely imported ``repro`` —
and the persistent compile cache goes where the start-up function says.
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")


def test_import_initialises_no_backend():
    code = (
        "import repro.views, repro.streaming, repro.planner\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, sorted(xla_bridge._backends)\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]


def _restore_cache_dir(prev):
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    from repro.compile_cache import REPO_CACHE, configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        got = configure_compile_cache()
        assert got == str(REPO_CACHE)
        assert REPO_CACHE.name == ".jax_cache"
        assert (REPO_CACHE.parent / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE)
    finally:
        _restore_cache_dir(prev)


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    from repro.compile_cache import configure_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert configure_compile_cache() == str(tmp_path)
        # JAX reads the variable itself: the code sets no directory
        assert jax.config.jax_compilation_cache_dir == prev
    finally:
        _restore_cache_dir(prev)
