"""Host-side telemetry collection over the in-device ring buffer.

The hot path stays jit-pure: compiled steps write sketch metrics into
``core.monitor.MonitorState`` (the ring buffer that already lives in
the train state / serve monitor state) and the helpers here DRAIN that
state on the host — one small (window, L, 3) device->host copy — into
``TelemetryRecord`` fields. Nothing here is ever traced.

``span`` provides the scoped wall-clock timers the schema's ``spans``
field expects: async dispatch means a bare ``perf_counter`` around a
jitted call measures dispatch, not work — the context manager blocks on
the arrays you hand it before reading the clock.
"""
from __future__ import annotations

import contextlib
import time
from typing import TYPE_CHECKING

import jax
import numpy as np

if TYPE_CHECKING:
    from repro.core.monitor import MonitorState, PathologyThresholds

# `repro.core.monitor` is imported where it is used: the model modules
# import this package for their scope names (`instrument.scope`), and
# `repro.core` imports them.


def latest_reading(state: MonitorState) -> np.ndarray | None:
    """The most recently written (L, N_METRICS) row of the ring, or
    None for an empty (freshly initialized) buffer."""
    count = int(state.count)
    if count == 0:
        return None
    window = state.buffer.shape[0]
    idx = (int(state.idx) - 1) % window
    return np.asarray(state.buffer[idx])


def node_metrics(reading: np.ndarray | None,
                 paths: list[str]) -> dict:
    """{node_path: {metric_name: float}} from one tree_metrics row —
    the schema's ``nodes`` field. Empty for a warming-up ring."""
    if reading is None:
        return {}
    from repro.core.monitor import METRIC_NAMES

    if reading.shape[0] != len(paths):
        raise ValueError(
            f"reading has {reading.shape[0]} rows but {len(paths)} "
            f"node paths — ring and tree are out of sync")
    return {
        path: {name: float(reading[i, j])
               for j, name in enumerate(METRIC_NAMES)}
        for i, path in enumerate(paths)
    }


def flag_paths(flags: dict, paths: list[str]) -> dict:
    """Resolve detect_pathologies' boolean (L,) arrays to node paths —
    the schema's ``flags`` field. Only non-empty pathologies appear."""
    out = {}
    for name, mask in flags.items():
        hit = [paths[i] for i, f in enumerate(np.asarray(mask)) if f]
        if hit:
            out[name] = hit
    return out


def monitor_report(state: MonitorState, paths: list[str], k_active: int,
                   th: PathologyThresholds | None = None,
                   ) -> tuple[dict, dict]:
    """One-stop drain: (nodes, flags) for a TelemetryRecord from the
    device ring buffer (default thresholds unless `th` is given). Safe
    on an empty ring (both empty)."""
    from repro.core.monitor import PathologyThresholds, detect_pathologies

    reading = latest_reading(state)
    if reading is None:
        return {}, {}
    th = PathologyThresholds() if th is None else th
    flags = jax.device_get(detect_pathologies(state, k_active, th))
    return node_metrics(reading, paths), flag_paths(flags, paths)


@contextlib.contextmanager
def span(spans: dict, name: str):
    """Scoped wall-clock timer accumulating into ``spans[name]``, inside
    a ``jax.profiler.TraceAnnotation(name)``: a profiled run shows the
    span on the host's timeline, on the clock of the device's ops.

        with span(spans, "decode") as block:
            out = step(...)
            block(out)          # block_until_ready before the clock read

    ``block`` may be called any number of times (0 = dispatch-only
    timing); it returns its argument so it nests in expressions.
    """
    pending = []

    def block(x):
        pending.append(x)
        return x

    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()
        try:
            yield block
        finally:
            for x in pending:
                jax.block_until_ready(x)
            spans[name] = spans.get(name, 0.0) + time.perf_counter() - t0
