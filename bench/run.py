#!/usr/bin/env python3
"""Runs one benchmark cell once and prints its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix, its per-layer metrics and
its limits are found by name (`bench/spec.py`). With `--trace 0` the
result carries the cell's end-to-end metrics; with `--trace 1` a short
traced window gives its per-layer metrics, the device's busy time and
a breakdown. Every run compares what its own first steps produced with
the plain reference and prints each compared number beside its limit,
on standard error and under "checks", the last key of the result.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result: it never carries on on the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime writes its logs to a fixed path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

PLATFORM = "tpu"
GIB = 2.0 ** 30
STEP_MODULE = "train_step"
# the steps the reference follows, the steps that settle before the
# window opens, and the length of the traced window of a `--trace 1` run
CHECK_STEPS = 3
SETTLE_STEPS = 3
TRACE_SECONDS = 4.0


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def require_chips(n: int, platform: str = PLATFORM) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise SystemExit(f"bench: no {platform.upper()} found — JAX sees "
                         f"{len(devs)} {devs[0].platform} device(s)")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, found "
                         f"{len(devs)}")
    return devs[:n]


class Context:
    """What a per-layer metric's reader may read: the cell, the window's
    device trace, and a way to run one layer alone under the profiler."""

    def __init__(self, c, arch, run, seed, device_kind, tmp):
        from bench import flops

        self.cell, self.arch, self.run, self.seed = c, arch, run, seed
        self.device_kind = device_kind
        self.tmp = tmp
        self.window_trace = None
        self.window = None            # (lo, hi) microseconds
        self.step_module = STEP_MODULE
        self.flops_per_step = flops.train_step(
            c.config["model"], c.traffic)["flops"]
        self._n = 0

    def seconds_alone(self, name: str, fn, args, reps: int = 5,
                      feed=None):
        """Device seconds of one call of jitted `fn` (its program named
        `jit_<name>`), the median over `reps` profiled calls after a
        warm-up call. `feed(out, args)` gives the next call's arguments
        where `fn` donates them."""
        import jax
        import numpy as np

        from bench import trace

        nxt = feed or (lambda out, a: a)
        out = fn(*args)
        jax.block_until_ready(out)
        args = nxt(out, args)
        self._n += 1
        d = os.path.join(self.tmp, f"alone{self._n}")
        jax.profiler.start_trace(d, create_perfetto_trace=True)
        try:
            for _ in range(reps):
                out = fn(*args)
                args = nxt(out, args)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        del out, args
        runs = trace.module_runs_us(trace.Trace.load(trace.find(d)),
                                    f"jit_{name}")
        return float(np.median(runs)) * 1e-6 if runs else None


def gc_summary(pauses) -> str:
    full = [p for g, p in pauses if g == 2]
    return (f"{len(pauses)} collections, {sum(p for _, p in pauses):.4f} s"
            f" in all, longest {max((p for _, p in pauses), default=0):.4f}"
            f" s; {len(full)} full, longest "
            f"{max(full, default=0):.4f} s")


def end_to_end(name: str, *, numbers: dict, setup_s: float,
               hbm_bytes: int):
    values = {"tokens_per_s": numbers["tokens_per_s"],
              "step_s_p95": numbers["step_s_p95"],
              "step_hbm_gib": hbm_bytes / GIB,
              "setup_s": setup_s}
    if name not in values:
        raise KeyError(f"the harness computes no end-to-end metric "
                       f"{name!r}")
    return values[name]


def run_cell(c, *, seed: int, seconds: float, trace: bool,
             t_start: float = T_START, platform: str = PLATFORM) -> dict:
    """One run of a training cell. Returns the result object."""
    import jax

    from bench import compare, reference, spec
    from bench import trace as tr
    from bench.train_cell import (
        CompileCounter, run_window, step_hbm_bytes, window_numbers,
    )
    from repro.launch.compile_cache import enable_compile_cache

    devs = require_chips(c.chips, platform)
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    arch, run = spec.arch_config(c), spec.run_config(c)
    t = c.traffic
    tokens_per_step = t["batch"] * t["seq_len"]
    tmp = tempfile.mkdtemp(prefix="bench_")
    try:
        ctx = Context(c, arch, run, seed, devs[0].device_kind, tmp)
        win_dir = os.path.join(tmp, "window")
        hooks = {}
        if trace:
            hooks = dict(
                on_open=lambda: jax.profiler.start_trace(
                    win_dir, create_perfetto_trace=True),
                on_close=jax.profiler.stop_trace)
        counter = CompileCounter()
        w = run_window(arch, run, seed=seed,
                       seconds=TRACE_SECONDS if trace else seconds,
                       check=CHECK_STEPS, settle=SETTLE_STEPS,
                       counter=counter, **hooks)
        setup_s = w.t_open - t_start
        numbers = window_numbers(w, tokens_per_step)
        peaks = [d.memory_stats() or {} for d in devs]
        peak = max((p.get("peak_bytes_in_use", 0) for p in peaks),
                   default=0)
        hbm = step_hbm_bytes(w.memory)
        say(f"[setup] {setup_s:.3f} s; programs lowered before the "
            f"window {w.lowerings_setup}; backend compile "
            f"{w.compile_s:.3f} s; compile cache {cache}")
        say(f"[memory] compiled step {hbm} B "
            f"(arguments {w.memory.argument_size_in_bytes}, outputs "
            f"{w.memory.output_size_in_bytes}, temporaries "
            f"{w.memory.temp_size_in_bytes}, aliased "
            f"{w.memory.alias_size_in_bytes}); peak_bytes_in_use {peak}")
        say(f"[gc] set-up: {gc_summary(w.gc.before)}; one more full "
            f"collection {w.gc.setup_s:.4f} s ({w.gc.setup_freed} objects "
            f"freed), then frozen; window: {gc_summary(w.gc.pauses)}")
        say(f"[window] {numbers['steps']} steps in "
            f"{numbers['seconds']:.4f} s; median step "
            f"{numbers['step_s_median']:.5f} s; longest intervals "
            f"(step, s) {numbers['longest']}")

        metrics, device_extra, breakdown = {}, {}, None
        if trace:
            ctx.window_trace = tr.Trace.load(tr.find(win_dir))
            ctx.window = tr.window(ctx.window_trace, STEP_MODULE)
            if ctx.window is not None:
                lo, hi = ctx.window
                device_extra = {
                    "busy_s": tr.busy_us(ctx.window_trace, lo, hi) * 1e-6,
                    "window_s": (hi - lo) * 1e-6}
                breakdown = tr.breakdown(ctx.window_trace, lo, hi)
            for m in c.per_layer:
                v = spec.metric_module(m["name"]).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            for m in c.end_to_end:
                metrics[m["name"]] = {
                    "value": end_to_end(m["name"], numbers=numbers,
                                        setup_s=setup_s, hbm_bytes=hbm),
                    "unit": m["unit"]}

        ref = reference.run(spec.reference_spec(c), seed,
                            steps=CHECK_STEPS)
        prog = {"loss": w.loss, "grad_norm": w.grad_norm,
                "first_grad": w.first_grad, "first_sample": w.first_sample,
                "change": w.change, "batches": w.batches}
        read = compare.cell_readings(t, prog, ref)
        ok, checks = compare.judge(read, c.limits)
        checks["window_compiles"] = {"value": numbers["window_compiles"],
                                     "limit": 0}
        ok = ok and numbers["window_compiles"] == 0
        failed = sum(1 for m in w.marks if not math.isfinite(m.loss))
        say(f"[compare] program loss {w.loss} reference {ref['loss']}; "
            f"grad norm {w.grad_norm} reference {ref['grad_norm']}; "
            f"readings {({n: v for n, v in read.items() if n != 'where'})}; "
            f"worst leaves: first gradient "
            f"{max(read['where']['first_grad'].items(), key=lambda kv: kv[1])}"
            f", change "
            f"{max(read['where']['change'].items(), key=lambda kv: kv[1])}")
        for n, v in checks.items():
            say(f"[check] {n} {v['value']} limit {v['limit']}")
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs),
                  "memory_peak_bytes": peak, **device_extra}
        out = {"correct": bool(ok and failed == 0),
               "attempted": numbers["steps"], "failed": failed,
               "metrics": metrics, "device": device}
        if breakdown is not None:
            out["breakdown"] = breakdown
        out["checks"] = checks
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec

    c = spec.cell(args.workload)
    out = run_cell(c, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
