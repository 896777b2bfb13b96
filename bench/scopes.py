"""In-step readings of the window's own trace: device time by the
program's named scopes, and device idle time under the loop's host
spans.

The program opens a `jax.named_scope` inside each layer's function and
runs each loop step as host spans that are also profiler annotations
(`repro.telemetry`, DESIGN.md §11). The names below are that contract.
They are written out here, not imported, because the benchmark also
reads traces of programs that have no such names; its readers then find
nothing and return None.

- Each device microsecond of the window is put down once, to the
  innermost operation that covers it: a `while` op on the "XLA Ops"
  line encloses its body's ops, and keeps only the time between them.
- An operation's scope is the first of `SCOPES` that is a component of
  its `op_name` metadata, which the Perfetto export keeps in the `tf_op`
  arg ("jit(train_step)/jvp()/while/body/closed_call/attention/dot:"),
  else `OTHER`.
- Per step: over the number of train-step program runs in the window.
"""
from __future__ import annotations

from bench import trace

SCOPES = ("attention", "sketch_update", "reconstruct", "adamw")
OTHER = "other"
FEED_SPAN = "loop/feed"
SYNC_SPAN = "loop/sync"
OP_NAME_ARG = "tf_op"


def scope_of(op_name: str) -> str:
    """The first of `SCOPES` found as a component of an `op_name` path
    (each component read up to any ':'), else `OTHER`."""
    for part in op_name.split("/"):
        part = part.split(":", 1)[0]
        if part in SCOPES:
            return part
    return OTHER


def device_us_by_scope(tr: trace.Trace, lo: float, hi: float) -> dict:
    """{scope or OTHER: device microseconds inside [lo, hi)}, each
    microsecond counted once, for the innermost op covering it; averaged
    over devices. The values sum to `trace.busy_us`."""
    pids = tr.device_pids()
    out = dict.fromkeys(SCOPES + (OTHER,), 0.0)
    for p in pids:
        ops = sorted(((o["ts"], o["ts"] + o["dur"],
                       scope_of(o.get("args", {}).get(OP_NAME_ARG, "")))
                      for o in tr.ops(p)), key=lambda o: (o[0], -o[1]))
        points = sorted({t for s, e, _ in ops for t in (s, e)})
        stack: list = []
        i = 0
        for t, t_next in zip(points, points[1:]):
            while i < len(ops) and ops[i][0] <= t:
                stack.append(ops[i])
                i += 1
            # the latest-started op still running is the innermost
            while stack and stack[-1][1] <= t:
                stack.pop()
            d = min(t_next, hi) - max(t, lo)
            if stack and d > 0:
                out[stack[-1][2]] += d / len(pids)
    return out


def step_ms(ctx, name: str) -> float | None:
    """Device milliseconds per train step in scope `name`, or None where
    the window's ops carry no scope at all."""
    if ctx.window is None:
        return None
    tr = ctx.window_trace
    by = device_us_by_scope(tr, *ctx.window)
    steps = [e for p in tr.device_pids()[:1]
             for e in tr.modules(p, ctx.step_module)]
    if not steps or not any(by[s] for s in SCOPES):
        return None
    return by[name] / len(steps) * 1e-3


def overlap_us(a: list, b: list) -> float:
    """Length of the intersection of two sorted lists of disjoint
    [start, end) intervals."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        tot += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_share_under(ctx, name: str) -> float | None:
    """% of the window in which the device was idle while the host span
    `name` was open, or None where the trace holds no such span."""
    if ctx.window is None:
        return None
    tr = ctx.window_trace
    lo, hi = ctx.window
    host = set(tr.host_pids())
    spans = [(e["ts"], e["ts"] + e["dur"]) for e in tr.events
             if e["pid"] in host and e["name"] == name]
    if not spans:
        return None
    idle = overlap_us(trace.union(spans, lo, hi), trace.gaps_us(tr, lo, hi))
    return 100.0 * idle / (hi - lo)
