"""Reduction of a profiler trace to the benchmark's device numbers.

Reads the Perfetto JSON that `jax.profiler` writes beside its xplane
(`perfetto_trace.json.gz`): complete events ("ph": "X") with a start and
a duration in microseconds, on processes named after the device
(`/device:TPU:0`, ...) or the host (`/host:CPU`). On a device, the
thread "XLA Modules" holds one event per program run and "XLA Ops" one
per operation inside it.

- busy: the union of the operation intervals, per device, averaged over
  the devices;
- idle share: 1 - busy / window;
- device time per operation name;
- idle gaps, each named by the host event that covers it.
"""
from __future__ import annotations

import glob
import gzip
import json
import os

OPS_THREAD = "XLA Ops"
MODULES_THREAD = "XLA Modules"


class Trace:
    def __init__(self, doc: dict):
        ev = doc["traceEvents"] if isinstance(doc, dict) else doc
        self.proc = {e["pid"]: e["args"]["name"] for e in ev
                     if e.get("ph") == "M" and e.get("name") ==
                     "process_name"}
        self.thread = {(e["pid"], e["tid"]): e["args"]["name"] for e in ev
                       if e.get("ph") == "M" and e.get("name") ==
                       "thread_name"}
        self.events = [e for e in ev if e.get("ph") == "X"
                       and "dur" in e]

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            return cls(json.load(f))

    # ---- where events live ---------------------------------------------

    def device_pids(self) -> list:
        return sorted(p for p, n in self.proc.items()
                      if n.startswith("/device:") and "CPU" not in n)

    def host_pids(self) -> list:
        return sorted(p for p, n in self.proc.items()
                      if n.startswith("/host:"))

    def on(self, pid, thread: str) -> list:
        return [e for e in self.events if e["pid"] == pid
                and self.thread.get((pid, e.get("tid"))) == thread]

    def ops(self, pid) -> list:
        return self.on(pid, OPS_THREAD)

    def modules(self, pid, contains: str = "") -> list:
        return sorted((e for e in self.on(pid, MODULES_THREAD)
                       if contains in e["name"]), key=lambda e: e["ts"])


def find(log_dir: str) -> str:
    """The newest Perfetto trace under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "perfetto_trace.json.gz"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no perfetto_trace.json.gz under {log_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def span(events) -> tuple[float, float]:
    return (min(e["ts"] for e in events),
            max(e["ts"] + e["dur"] for e in events))


def window(tr: Trace, module: str) -> tuple[float, float] | None:
    """From the start of the first to the end of the last run of the
    program named `module` on any device (microseconds)."""
    runs = [e for p in tr.device_pids() for e in tr.modules(p, module)]
    return span(runs) if runs else None


def busy_us(tr: Trace, lo: float, hi: float) -> float:
    """Device time in which some operation ran, averaged over devices."""
    pids = tr.device_pids()
    tot = 0.0
    for p in pids:
        tot += sum(e - s for s, e in union(
            ((o["ts"], o["ts"] + o["dur"]) for o in tr.ops(p)), lo, hi))
    return tot / max(len(pids), 1)


def op_times_us(tr: Trace, lo: float, hi: float) -> dict:
    """{operation name: device microseconds inside [lo, hi)}, averaged
    over devices."""
    pids = tr.device_pids()
    out: dict = {}
    for p in pids:
        for o in tr.ops(p):
            d = min(o["ts"] + o["dur"], hi) - max(o["ts"], lo)
            if d > 0:
                out[o["name"]] = out.get(o["name"], 0.0) + d / len(pids)
    return out


def gaps_us(tr: Trace, lo: float, hi: float, pid=None) -> list:
    """Idle intervals [(start, end)] of one device inside [lo, hi)."""
    pid = tr.device_pids()[0] if pid is None else pid
    busy = union(((o["ts"], o["ts"] + o["dur"]) for o in tr.ops(pid)),
                 lo, hi)
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gap(tr: Trace, s: float, e: float) -> str:
    """The host event that covers an idle gap: the shortest one that
    covers at least half of it, else the one that covers most."""
    host = [h for h in tr.events if h["pid"] in set(tr.host_pids())]
    ov = lambda h: min(h["ts"] + h["dur"], e) - max(h["ts"], s)
    cover = [(h["dur"], h["name"]) for h in host if ov(h) >= 0.5 * (e - s)]
    if cover:
        return min(cover)[1]
    part = [(ov(h), h["name"]) for h in host if ov(h) > 0]
    return max(part)[1] if part else "host: no event"


def breakdown(tr: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The device operations that took most time and the longest idle
    gaps, named by what the host was doing, in seconds."""
    ops = sorted(op_times_us(tr, lo, hi).items(), key=lambda kv: -kv[1])
    gaps = sorted(gaps_us(tr, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, t * 1e-6] for n, t in ops[:top]],
        "idle_gaps": [[name_gap(tr, s, e), (e - s) * 1e-6]
                      for s, e in gaps],
    }


def module_runs_us(tr: Trace, module: str) -> list:
    """Device durations of each run of the program named `module`."""
    return [e["dur"] for p in tr.device_pids()[:1]
            for e in tr.modules(p, module)]
