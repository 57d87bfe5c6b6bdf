"""The harness end to end on the CPU at a tiny scale, with the chip check
skipped: a sound run is correct; the bf16 control and each planted fault
of the timed path are not; off the TPU the command fails."""

import copy
import json
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

from svcbench.harness import Run, load_cell, load_limits

REPO = pathlib.Path(__file__).resolve().parent.parent
CELL = "tpch_sf1_2view.adhoc"


def tiny(cell, scale=0.01):
    """The cell at a CPU-sized scale, with order keys reserved for as
    many RF1 lineitems as the set-up's backlog and the window insert."""
    cell = copy.deepcopy(cell)
    cfg = cell["config"]
    cfg["rows"] = {k: max(50, int(v * scale)) for k, v in cfg["rows"].items()}
    cfg["group_slack"] = 2.0
    for v in cfg["views"]:
        if v.get("outlier"):
            v["outlier"][1] = 16
    mix = cell["mix"]
    mix["inserts"].update(micro_batch_rows=512, rate_rows_per_s=512 * 4.0,
                          watermark_batches=4)
    mix["queries"]["rate_per_s"] = 4.0
    mix.update(backlog_rows=17000, warm_epochs=2, warm_query_batches=1,
               trace_subwindow_s=1.0)
    return cell


def run_tiny(seed, tmp_path, control=False):
    cell = tiny(load_cell(CELL))
    run = Run(cell, seed, 3.0, False, require_tpu=False, out_dir=tmp_path,
              log=lambda m: None)
    run.setup()
    run.window()
    run.collect()
    checks = run.check(control=control)
    limits = load_limits(CELL)
    return run, checks, all(v <= limits[k] for k, v in checks.items())


def test_sound_run_is_correct(tmp_path):
    run, checks, ok = run_tiny(11, tmp_path)
    assert ok, checks
    assert run.compiles_in_window == 0
    assert any(a.answers for a in run.asked)
    assert sum(1 for e in run.epochs if e.t0 >= run.t_open) >= 1
    e2e = run.end_to_end()
    assert all(e2e[f"query_p50_ms.{n}"] > 0 for n in run.names)
    assert e2e["query_p95_ms"] > 0 and e2e["fresh_lag_p95_s"] > 0


def test_control_is_not_correct(tmp_path):
    _run, checks, ok = run_tiny(12, tmp_path, control=True)
    assert not ok, checks


def _unchanged_clean(monkeypatch):
    import repro.core.maintenance as maint
    import repro.views.manager as manager

    monkeypatch.setattr(manager, "clean_sample",
                        lambda strategy, name, pk, stale, *a, **k: stale)
    monkeypatch.setattr(maint, "fleet_clean_merge", lambda jobs: ({}, {}))


def _half_batch(monkeypatch):
    import repro.query.engine as engine

    inner = engine.multi_agg_moments

    def half(*a, **k):
        # every other row of the (aligned) panel left out, on both sides
        a = list(a)
        keep = np.arange(a[1].shape[0]) % 2 == 0
        a[1] = a[1] & keep
        if len(a) > 7 and a[7] is not None:
            a[7] = a[7] & keep
        return inner(*a, **k)

    monkeypatch.setattr(engine, "multi_agg_moments", half)


def _altered_answer(monkeypatch):
    import dataclasses

    import repro.views.manager as manager

    inner = manager.run_batch

    def altered(*a, **k):
        out = inner(*a, **k)
        return [dataclasses.replace(e, value=e.value * (1 + 1e-2)) for e in out]

    monkeypatch.setattr(manager, "run_batch", altered)


def _dropped_batch(monkeypatch):
    from repro.streaming import delta_log

    inner = delta_log.DeltaLog.offer
    seen = {"n": 0}

    def drop(self, *a, **k):
        seen["n"] += 1
        if seen["n"] % 7 == 0:
            return None
        return inner(self, *a, **k)

    monkeypatch.setattr(delta_log.DeltaLog, "offer", drop)


@pytest.mark.parametrize("fault", [_unchanged_clean, _half_batch,
                                   _altered_answer, _dropped_batch],
                         ids=["state_unchanged", "half_batch", "answer_altered",
                              "batch_dropped"])
def test_planted_fault_is_not_correct(fault, monkeypatch, tmp_path):
    fault(monkeypatch)
    _run, checks, ok = run_tiny(13, tmp_path)
    assert not ok, checks


def test_off_the_tpu_the_command_fails():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run(
        [sys.executable, str(REPO / "svcbench" / "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_without_the_program_the_command_fails(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "svcbench", tmp_path / "svcbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "svcbench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env={"JAX_PLATFORMS": "cpu",
                                             "PATH": "/usr/bin:/bin"},
        cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_benchmark_file_names_existing_parts():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    root = REPO / "svcbench"
    for w in bench["workloads"]:
        assert (root / "traffic" / f"{w['traffic']}.json").exists()
        assert (root / "limits" / f"{w['name']}.json").exists()
    for c in bench["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert (root / "datasets" / f"{cfg['dataset']}.py").exists()
    for m in bench["per_layer"]:
        assert (root / "metrics" / f"{m['name']}.py").exists()
