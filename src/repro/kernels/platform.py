"""Which path a kernel op takes, decided at its first call, never at import.

Importing ``repro`` must not initialise a JAX backend: on a TPU host that
would claim the chip for whichever process merely imported the package.
So every ``kernels/*/ops.py`` asks here at dispatch time, and the backend
is read once, on the first call, and cached.

* ``use_pallas(flag)`` — the op's path: the caller's ``use_pallas=``
  argument when given (tests force either path), else the Pallas kernel on
  a TPU and the XLA reference math elsewhere.
* ``interpret()`` — a Pallas kernel runs in interpret mode only on a
  backend that is not a TPU; on a TPU it is compiled by Mosaic, and a
  kernel Mosaic refuses raises instead of silently interpreting.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax


@functools.cache
def on_tpu() -> bool:
    """True when JAX's default backend is a TPU (read on first call)."""
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    return not on_tpu()


def use_pallas(flag: Optional[bool] = None) -> bool:
    return on_tpu() if flag is None else bool(flag)
