"""Counts what JAX lowers or compiles, so a run can show that nothing
compiled inside its measured window."""
from __future__ import annotations

import jax

_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
           "/jax/core/compile/backend_compile_duration")


class CompileWatch:
    """``count`` rises by one for every lowering and every backend
    compile in this process from ``install()`` on. A new shape lowers
    even where the persistent cache then spares the compile, so a shape
    that warm-up missed is counted on warm runs too."""

    def __init__(self):
        self.count = 0
        self.names = []
        self._installed = False

    def install(self):
        if not self._installed:
            jax.monitoring.register_event_duration_secs_listener(self._on)
            self._installed = True
        return self

    def _on(self, event, duration, **kw):
        if event in _EVENTS:
            self.count += 1
            self.names.append(kw.get("fun_name", event.rsplit("/", 1)[-1]))
