"""Kernel microbenchmarks: correctness deltas vs oracles + interpret-mode
call timing (CPU wall time is NOT the TPU target metric — the structural
analysis lives in the roofline; this proves the kernels run and agree).

Also reports the arithmetic-intensity argument for the fused
sketch_update kernel (DESIGN.md §7): 3 separate projections re-read A
three times; fusion reads once.

The p-sparsified section (DESIGN.md §13) is the one place on this CPU
container where wall-clock IS the metric: the dense jnp production
update and the psparse gather fast path hit the same BLAS backend, so
their time RATIO measures the structural T -> m contraction shrink the
kernel realizes on TPU. Gated: psparse must stay >= {floor}x faster
than dense at every density, and the committed BENCH_sketch_update.json
pins the ratios against 10% regression (shared `check_baseline`
machinery). The committed ratio baselines are hand-rounded CEILINGS
(~2.5x the best observed, still well under the 1/{floor} bar) so CPU
timing jitter never trips the gate while a real regression — psparse
losing its structural advantage — still does; `--json` writes the raw
measured ratios for nightly trend artifacts.

Usage:
  PYTHONPATH=src python benchmarks/bench_kernels.py \\
         [--json artifacts/BENCH_sketch_update.json] \\
         [--baseline BENCH_sketch_update.json]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention, mlstm_chunk, sketch_update
from repro.kernels.ref import (
    flash_attention_ref, mlstm_chunk_ref, psparse_update_ref,
    sketch_update_ref,
)

# relative gates of BENCH_sketch_update.json: psparse/dense time ratios
# (lower = better; >10% above the committed baseline fails CI)
SKETCH_UPDATE_GATES = (
    "psparse_time_ratio_p05",
    "psparse_time_ratio_p10",
    "psparse_time_ratio_p20",
)
PSPARSE_SPEEDUP_FLOOR = 3.0      # absolute acceptance bar (ISSUE 8)


def timeit(fn, *args, n=3):
    fn(*args)
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6


def timeit_min(fn, *args, n=5):
    """Best-of-n single-call time (us) after two warmups — robust to
    background load, which the mean is not (the psparse/dense RATIO
    gates below ride on this)."""
    fn(*args)
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_psparse(metrics: dict) -> list[tuple]:
    """Dense-vs-psparse sketch update at p in {0.05, 0.1, 0.2}:
    correctness (Pallas kernel BITWISE vs its jnp oracle; gather fast
    path allclose vs the dense materialization), measured wall-clock
    speedup, and the FLOP/HBM accounting cross-checked against the
    analytic roofline constants."""
    from benchmarks.analytic import HBM_BW, PEAK_FLOPS
    from repro.kernels.psparse_update import psparse_update
    from repro.sketches import init_psparse_projections
    from repro.sketches.update import (
        ema_triple_update, mask_columns, proj_triple_update,
    )

    rows = []
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 7)

    # correctness at a kernel-friendly small shape (interpret mode)
    T0, d0, k0 = 256, 128, 33
    a0 = jax.random.normal(ks[0], (T0, d0))
    s0 = 0.1 * jax.random.normal(ks[1], (d0, k0))
    psi0 = jax.random.normal(ks[2], (k0,))
    proj0 = init_psparse_projections(ks[3], T0, k0, 0.1)
    got = psparse_update(a0, s0, s0, s0, proj0.params, psi0,
                         beta=0.9, m=proj0.m)
    want = psparse_update_ref(a0, s0, s0, s0, proj0.params, psi0,
                              beta=0.9, m=proj0.m)
    bitwise = all(bool((g == w).all()) for g, w in zip(got, want))
    metrics["psparse_kernel_bitwise"] = float(not bitwise)  # 0 == pass
    rows.append(("psparse_kernel_vs_ref", 0.0 if bitwise else float(
        max(jnp.abs(g - w).max() for g, w in zip(got, want))),
        f"bitwise={bitwise} (CPU interpret; Mosaic: allclose)"))
    assert bitwise, "psparse kernel diverged from its jnp oracle"

    # gather fast path vs the dense materialization of the SAME
    # implicit matrix (the oracle every consumer sees via __getitem__)
    ka0 = jnp.asarray(k0)
    fast = proj_triple_update(s0, s0, s0, a0, proj0, psi0, 0.9, ka0,
                              use_kernel=False)
    dense0 = ema_triple_update(
        s0, s0, s0, a0, proj0["upsilon"], proj0["omega"], proj0["phi"],
        psi0, 0.9, ka0, use_kernel=False)
    err = float(max(jnp.abs(mask_columns(g, ka0) -
                            mask_columns(w, ka0)).max()
                    for g, w in zip(fast, dense0)))
    rows.append(("psparse_fastpath_vs_dense", err, "same implicit matrix"))
    assert err < 1e-4, err

    # wall-clock: production jnp paths at a training-sized node
    T, d, k = 4096, 1024, 33
    a = jax.random.normal(ks[4], (T, d))
    x = jnp.zeros((d, k))
    ups, omg, phi = (jax.random.normal(ks[i], (T, k)) for i in (4, 5, 6))
    psi = jax.random.normal(ks[2], (k,))
    ka = jnp.asarray(k)
    f_dense = jax.jit(lambda aa, xx: ema_triple_update(
        xx, xx, xx, aa, ups, omg, phi, psi, 0.9, ka, use_kernel=False))
    t_dense = timeit_min(f_dense, a, x)

    # accounting conventions (cross-checked vs benchmarks/analytic.py):
    # dense reads A once fused (T*d floats) + three (T,k) projections,
    # 6 d*k sketch in/out; flops = 3 GEMM contractions over T.
    dense_flops = 3 * 2 * T * d * k
    dense_bytes = T * d * 4 + 3 * T * k * 4 + 6 * d * k * 4
    ridge = PEAK_FLOPS / HBM_BW
    for p, tag in ((0.05, "p05"), (0.1, "p10"), (0.2, "p20")):
        proj = init_psparse_projections(ks[3], T, k, p)
        m = proj.m
        f_ps = jax.jit(lambda aa, xx, pr=proj: proj_triple_update(
            xx, xx, xx, aa, pr, psi, 0.9, ka, use_kernel=False))
        t_ps = timeit_min(f_ps, a, x)
        speedup = t_dense / t_ps
        metrics[f"psparse_time_ratio_{tag}"] = t_ps / t_dense
        metrics[f"psparse_speedup_{tag}"] = speedup
        # psparse touches only the m hashed support rows of A (x3, one
        # implicit matrix each), 48 B of coefficients, same sketch I/O:
        # the memory-bound floor the kernel's on-the-fly generation
        # reaches (nothing dense ever lands in HBM).
        ps_flops = 3 * 2 * m * d * k
        ps_bytes = 3 * m * d * 4 + 3 * 16 + 6 * d * k * 4
        ai_dense = dense_flops / dense_bytes
        ai_ps = ps_flops / ps_bytes
        analytic_dense = max(dense_flops / PEAK_FLOPS,
                             dense_bytes / HBM_BW)
        analytic_ps = max(ps_flops / PEAK_FLOPS, ps_bytes / HBM_BW)
        regime = "memory" if ai_ps < ridge else "compute"
        rows.append((
            f"psparse_{tag}", 0.0,
            f"m={m}/{T} speedup={speedup:.1f}x "
            f"flop_ratio={dense_flops / ps_flops:.1f} "
            f"byte_ratio={dense_bytes / ps_bytes:.1f} "
            f"AI {ai_dense:.0f}->{ai_ps:.0f} ({regime}-bound, "
            f"ridge {ridge:.0f}) "
            f"analytic {analytic_dense * 1e6:.1f}->"
            f"{analytic_ps * 1e6:.1f}us"))
        assert speedup >= PSPARSE_SPEEDUP_FLOOR, (
            f"psparse p={p}: {speedup:.2f}x < "
            f"{PSPARSE_SPEEDUP_FLOOR}x floor")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable psparse metrics "
                         "(time ratios, speedups) as JSON")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="committed BENCH_sketch_update.json to gate "
                         "against (time-ratio regression beyond 10%% "
                         "fails)")
    args = ap.parse_args(argv)
    metrics: dict = {}

    key = jax.random.PRNGKey(0)
    rows = []

    # sketch_update
    T, d, k = 512, 512, 33
    ks = jax.random.split(key, 8)
    a = jax.random.normal(ks[0], (T, d))
    x = jnp.zeros((d, k)); y = jnp.zeros((d, k)); z = jnp.zeros((d, k))
    ups, omg, phi = (jax.random.normal(ks[i], (T, k)) for i in (1, 2, 3))
    psi = jax.random.normal(ks[4], (k,))
    got = sketch_update(a, x, y, z, ups, omg, phi, psi, beta=0.9)
    want = sketch_update_ref(a, x, y, z, ups, omg, phi, psi, 0.9)
    err = max(float(jnp.abs(g - w).max()) for g, w in zip(got, want))
    # fused reads A once: bytes = T*d*4 + 3*T*k*4 + 6*d*k*4; unfused 3x A
    fused = T * d * 4 + 3 * T * k * 4 + 6 * d * k * 4
    unfused = 3 * T * d * 4 + 3 * T * k * 4 + 6 * d * k * 4
    rows.append(("sketch_update", err,
                 f"hbm_saving={1 - fused/unfused:.2f}"))

    # flash attention
    q = jax.random.normal(ks[5], (2, 4, 128, 32))
    kk = jax.random.normal(ks[6], (2, 2, 128, 32))
    v = jax.random.normal(ks[7], (2, 2, 128, 32))
    got = flash_attention(q, kk, v, causal=True, window=64,
                          q_blk=32, kv_blk=32)
    want = flash_attention_ref(q, kk, v, causal=True, window=64)
    rows.append(("flash_attention", float(jnp.abs(got - want).max()), ""))

    # mlstm chunk
    q2 = jax.random.normal(ks[5], (1, 2, 64, 16))
    k2 = jax.random.normal(ks[6], (1, 2, 64, 16))
    v2 = jax.random.normal(ks[7], (1, 2, 64, 32))
    li = jax.random.normal(ks[4], (1, 2, 64)) * 0.5
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[3], (1, 2, 64)) + 2)
    h_k, _ = mlstm_chunk(q2, k2, v2, li, lf, chunk=16)
    h_r, _ = mlstm_chunk_ref(q2, k2, v2, li, lf,
                             jnp.zeros((1, 2, 16, 32)),
                             jnp.zeros((1, 2, 16)), jnp.zeros((1, 2)),
                             16)
    rows.append(("mlstm_chunk", float(jnp.abs(h_k - h_r).max()), ""))

    rows.extend(bench_psparse(metrics))

    print("kernel,max_err_vs_oracle,notes")
    for name, err, note in rows:
        print(f"{name},{err:.2e},{note}")

    if args.json:
        from benchmarks.bench_countsketch import write_bench_json
        write_bench_json(args.json, metrics)
        print(f"json,written,{args.json},{len(metrics)} metrics")

    if args.baseline:
        from benchmarks.bench_countsketch import check_baseline
        failures = check_baseline(metrics, args.baseline,
                                  gates=SKETCH_UPDATE_GATES)
        if failures:
            print("baseline,gate,FAIL," + "; ".join(failures))
            raise SystemExit(
                "bench regression vs committed baseline:\n  " +
                "\n  ".join(failures))
        print(f"baseline,gate,PASS,psparse ratios within limits of "
              f"{args.baseline}")


if __name__ == "__main__":
    main()
