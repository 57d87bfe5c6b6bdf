"""Count the executables JAX builds, through ``jax.monitoring``.

Every jit specialization that needs an executable — compiled, or loaded
from the persistent cache — records one backend-compile duration event.
The harness reads the count before and after its window: anything built
inside the window was not warmed up in set-up.
"""

from __future__ import annotations

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"


class CompileCounter:
    def __init__(self):
        self.compiles = 0
        self.traces = 0
        self.compile_s = 0.0

    def _on(self, event: str, duration_s: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += duration_s
        elif event == TRACE:
            self.traces += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> bool:
        jax.monitoring.unregister_event_duration_listener(self._on)
        return False
