"""Names the profiler sees, and a count of what the process compiled
(DESIGN.md §11).

Device scopes: the functions that do a layer's work open
``with scope(<name>):`` (a ``jax.named_scope``) inside themselves, so
every operation they emit carries the name in its ``op_name``
metadata, in the train step and in any call of the function alone,
through ``jit``, ``grad``, ``remat`` and ``scan`` alike. A scope
changes only metadata, never the compiled program's buffers.

Host spans: ``train/loop.py`` splits each step into the ``LOOP_SPANS``
(``telemetry.span``, which also opens a ``jax.profiler.TraceAnnotation``
of the same name), inside one ``StepTraceAnnotation`` per step.

These names are a contract: the benchmark's readers find them in a
profiler trace by name, so a rename here needs the same rename there.

The compile counter is one process-wide ``jax.monitoring`` listener,
registered on first use.
"""
from __future__ import annotations

import jax

# the device scopes, one per layer the benchmark reports in the step
SCOPES = ("attention", "sketch_update", "reconstruct", "adamw")
# the host spans of one training step, in the order the loop runs them
LOOP_SPANS = ("loop/feed", "loop/dispatch", "loop/sync", "loop/control",
              "loop/record", "loop/ckpt")
# the name of each step's StepTraceAnnotation
STEP_ANNOTATION = "train"

_LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def scope(name: str):
    """``jax.named_scope(name)`` for one of ``SCOPES``: open it with
    ``with`` inside the function doing the work."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; the scopes are "
                         f"{SCOPES}")
    return jax.named_scope(name)


class CompileCounter:
    """Programs lowered for compilation (one per jit cache miss, whether
    or not the persistent cache then serves the executable) and backend
    compile seconds, since the counter was registered."""

    def __init__(self):
        self.lowerings = 0
        self.compile_s = 0.0

    def __call__(self, name, secs, **_):
        if name == _LOWERED:
            self.lowerings += 1
        elif name == _BACKEND_COMPILE:
            self.compile_s += secs


_counter: CompileCounter | None = None


def compile_counter() -> CompileCounter:
    """The process's one counter, registered with ``jax.monitoring`` on
    the first call."""
    global _counter
    if _counter is None:
        _counter = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(_counter)
    return _counter
