#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 12 \
        --control-seeds 3 --out DIR

For each seed: the program's first steps through the window's own loop
(`train_cell.run_window`, a window of one step) against the plain
reference. On the first `--control-seeds` seeds also the control (the
reference in float8, in the program's place) and each fault the cell can
have, planted in the reference put in the program's place: the state
returned unchanged, half of each row left out of the mean, one token
altered where the batch is made. Prints one JSON line per reading and
writes them all to DIR/<cell>.json. The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

FAULTS = ("frozen", "half_batch", "altered_token")


def calibrate(c, *, seeds: int, control_seeds: int, first_seed: int,
              platform: str = "tpu") -> list:
    """The readings of one cell, as a list of rows."""
    import jax

    from bench import compare, reference, spec
    from bench.run import CHECK_STEPS, require_chips
    from bench.train_cell import CompileCounter, run_window
    from repro.launch.compile_cache import enable_compile_cache

    require_chips(c.chips, platform)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    arch, run, rs = spec.arch_config(c), spec.run_config(c), \
        spec.reference_spec(c)
    steps = CHECK_STEPS
    counter = CompileCounter()
    rows = []

    def emit(kind, seed, prog, ref, t0):
        read = compare.cell_readings(c.traffic, prog, ref)
        row = {"cell": c.name, "kind": kind, "seed": seed,
               "seconds": time.perf_counter() - t0,
               **{n: v for n, v in read.items() if n != "where"},
               "where": read["where"]}
        rows.append(row)
        print(json.dumps(row), flush=True)

    for i in range(seeds):
        seed = first_seed + 7919 * i
        t0 = time.perf_counter()
        w = run_window(arch, run, seed=seed, seconds=0.0, check=steps,
                       settle=0, counter=counter)
        prog = {"loss": w.loss, "grad_norm": w.grad_norm,
                "first_grad": w.first_grad, "first_sample": w.first_sample,
                "change": w.change, "batches": w.batches}
        del w
        ref = reference.run(rs, seed, steps=steps)
        emit("program", seed, prog, ref, t0)
        if i >= control_seeds:
            continue
        t0 = time.perf_counter()
        ctl = reference.run(rs, seed, steps=steps, precision="fp8")
        emit("control_fp8", seed, ctl, ref, t0)
        for f in FAULTS:
            t0 = time.perf_counter()
            bad = reference.run(rs, seed, steps=steps, fault=f)
            emit(f"fault_{f}", seed, bad, ref, t0)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 - 1000)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from bench import spec

    c = spec.cell(args.workload)
    rows = calibrate(c, seeds=args.seeds, control_seeds=args.control_seeds,
                     first_seed=args.first_seed)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"{c.name}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
