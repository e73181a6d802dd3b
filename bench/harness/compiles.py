"""Counting JAX's compilations, and a checksum of a call's outputs."""
from __future__ import annotations

import hashlib

import numpy as np

#: JAX's monitoring events for tracing, lowering and XLA compilation
COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
))
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Seconds spent compiling and the number of XLA compilations."""

    def __init__(self):
        self.seconds = 0.0
        self.backend_compiles = 0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += secs
        if event == BACKEND_COMPILE:
            self.backend_compiles += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)


def checksum(out: dict) -> str:
    """sha256 over every output's name, dtype, shape and bytes."""
    h = hashlib.sha256()
    for k in sorted(out):
        a = np.ascontiguousarray(np.asarray(out[k]))
        h.update(f"{k}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]
