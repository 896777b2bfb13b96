"""Sketch-native telemetry (DESIGN.md §11).

One schema for train AND serve: compiled steps keep writing sketch
metrics into the in-device ring buffer (`core.monitor.MonitorState` —
the hot path stays jit-pure and recompile-free), and the host drains it
into ``TelemetryRecord``s exported as JSONL. ``run_metadata`` is the
shared attribution header for telemetry logs and the BENCH_*.json
baselines. ``instrument`` holds the names a profiler trace shows (the
device scopes, the loop's host spans) and the process's compile
counter.
"""
from repro.telemetry.schema import (
    RECORD_KINDS, SCHEMA_VERSION, TelemetryRecord, record_from_json,
    record_to_json, record_to_line, run_metadata,
)
from repro.telemetry.log import TelemetryLog, read_jsonl, scalarize
from repro.telemetry.collector import (
    flag_paths, latest_reading, monitor_report, node_metrics, span,
)
from repro.telemetry.instrument import (
    LOOP_SPANS, SCOPES, STEP_ANNOTATION, compile_counter, scope,
)

__all__ = [
    "LOOP_SPANS", "RECORD_KINDS", "SCHEMA_VERSION", "SCOPES",
    "STEP_ANNOTATION", "TelemetryLog", "TelemetryRecord", "compile_counter",
    "flag_paths", "latest_reading", "monitor_report", "node_metrics",
    "read_jsonl", "record_from_json", "record_to_json", "record_to_line",
    "run_metadata", "scalarize", "scope", "span",
]
