"""Compile accounting and the compile cache of a run.

Every new program JAX lowers emits one ``jaxpr_to_mlir_module`` duration
event, whether the persistent cache then serves it or the backend compiles
it, so counting those events before and after the window shows whether
anything compiled inside it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileCounter:
    """Programs lowered, and seconds spent tracing, lowering and compiling,
    since the counter was made. Registered once per process."""

    def __init__(self):
        self.programs = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == LOWER_EVENT:
            self.programs += 1
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def use_checkout_cache(root: Path) -> str:
    """Keep JAX's persistent compilation cache at the fixed ``.jax_cache``
    of the checkout, and cache every program however fast it compiles, so
    that only a checkout's first run compiles. The program's own cache rule
    (``repro.compile_cache``) reads the same variable."""
    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    from repro.compile_cache import enable_compile_cache
    got = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return got
