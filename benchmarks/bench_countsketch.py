"""Count-sketch DP compression benchmark (ISSUE 1 + 2 + 4 gates).

Sections:

  1. kernel      fused Pallas csvec_insert vs jnp reference: max error
                 + interpret-mode call timing (CPU wall time is not the
                 TPU target metric — parity is the point here).
  2. streaming   chunked heavy-hitter recovery vs the dense query_all
                 oracle: bit-exact candidate selection + peak
                 intermediate sizes from the jaxprs (O(chunk) vs
                 O(r * D)).
  3. wire        per-step all-reduce bytes: dense psum vs top-k vs the
                 count-sketch table (fp32 AND int8 + per-row scales).
                 The fp32 sketch must be <= 10% of dense, the int8 one
                 <= 2.5% — AND both are invariant to worker count,
                 since psum merges tables without concatenating
                 (unlike top-k indices).
  4. collectives per-collective wall time on a real W=4 shard_map mesh
                 (subprocess with 4 fake CPU devices): dense grad pmean
                 vs sketch-table psum vs the p2 value exchange vs the
                 fused flat-segment psum that replaces them all
                 (ISSUE 4: one collective per step).
  5. convergence the synthetic LM task trained with dense grads, top-k
                 and countsketch compression; final losses must match
                 within tolerance while countsketch ships ~10x fewer
                 bytes.
  6. w4_gate     ISSUE 2 acceptance: a REAL W=4 shard_map train run
                 with countsketch + p2 exchange must match the dense-
                 pmean W=4 run's final loss within tolerance at <= 10%
                 of its wire bytes.
  7. int8_gate   ISSUE 4 acceptance: the fused one-collective W=4 step
                 with the int8 count-sketch wire — wire bytes <= 2.5%
                 of dense at a matched-loss gap <= 0.05, with exactly
                 ONE collective per step in the compiled HLO.
  8. overlap_gate ISSUE 5 acceptance: the TWO-phase overlap W=4 step
                 with sketched-BACKPROP trees and the int8 wire — wire
                 bytes <= 2.5% of dense at loss gap <= 0.05 vs the
                 dense-wire overlap run, with exactly TWO all-reduces
                 per compiled step and the sketch psum scheduled first.
  9. int8_e2e    ISSUE 9 acceptance: int8 END-TO-END on the DP wire —
                 sketch increment segments (per-row scales, residual in
                 the per-worker sketch_err ledger) AND the count-sketch
                 table AND the overlapped p2 exact-value round. Gate:
                 TOTAL per-step wire <= 1% of the dense gradient psum
                 at a loss gap <= 0.05 vs the f32 wire, with zero
                 serial third collective (the fused HLO holds exactly
                 two all-reduces: the flat wire + the p2 round hidden
                 behind the zero-grad dense optimizer pass).
 10. mesh_gate   ISSUE 7 acceptance, structural half: per-axis
                 collective counts of the ZeRO-style reduce-scatter
                 sketch merge on the (pod=2, data=2, model=2) mesh —
                 RS + AG + wire AR on the flattened dp supergroup,
                 ZERO step-issued model-axis collectives — plus the
                 per-worker sketch-state bytes the shard buys. The W=8
                 differential tier proves the same numbers against
                 compiled HLO; this section pins them in the committed
                 baseline so a layout regression also shows up as a
                 bench diff.

Machine-readable output (ISSUE 5 CI): --json PATH writes every gated
metric (wire ratios, loss gaps, collective counts per section) as
BENCH_countsketch.json; --baseline PATH compares against a committed
baseline and FAILS on >10% regression of any wire ratio or collective
count (loss-gap gates stay absolute asserts). The committed baseline
lives at the repo root (BENCH_countsketch.json).

Run: PYTHONPATH=src python -m benchmarks.bench_countsketch \\
         [--json artifacts/BENCH_countsketch.json] \\
         [--baseline BENCH_countsketch.json]
(sections 4 and 6-8 spawn subprocesses with their own XLA_FLAGS).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp


TOL = 0.5          # matched-final-loss tolerance (nats) on the LM task
STEPS = 40
LAST = 5           # average the last LAST losses
W4_STEPS = 30      # steps for the W=4 shard_map gate run
I8_STEPS = 20      # steps for the int8 one-collective gate: the dense-
#                    vs-compressed trajectory gap GROWS with horizon for
#                    any top-k-style compressor (0.036 @ 20 steps,
#                    0.054 @ 30, 0.076 @ 50 measured for this config) —
#                    the 0.05 budget is pinned at a fixed 20-step
#                    horizon; past it the lever is the p2 exact-value
#                    round (gap 0.049 @ 30 steps at 2.2% wire with
#                    cs_p2=1/cs_cols=1024), which adds the one
#                    documented second collective


def _timeit(fn, *args, n=3):
    fn(*args)
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6


def bench_kernel():
    from repro.countsketch import make_csvec
    from repro.kernels.csvec_insert import csvec_insert
    from repro.kernels.ref import csvec_insert_ref

    key = jax.random.PRNGKey(0)
    dim, rows, cols = 100_000, 5, 2048
    cs = make_csvec(key, dim=dim, rows=rows, cols=cols)
    v = jax.random.normal(jax.random.fold_in(key, 1), (dim,))
    got = csvec_insert(cs.table, cs.params, v)
    want = csvec_insert_ref(cs.table, cs.params, v)
    rel = float(jnp.abs(got - want).max() /
                jnp.maximum(jnp.abs(want).max(), 1e-12))
    us = _timeit(lambda x: csvec_insert(cs.table, cs.params, x), v)
    # one HBM pass: n floats read + r*c table resident in VMEM; the
    # naive path re-reads (or re-gathers) per hash row
    hbm_fused = dim * 4 + rows * cols * 4
    hbm_naive = rows * dim * 4 + rows * cols * 4
    return [("csvec_insert", f"rel_err={rel:.2e}",
             f"interpret_us={us:.0f}",
             f"hbm_saving={1 - hbm_fused / hbm_naive:.2f}")]


def bench_streaming():
    from repro.countsketch import insert, make_csvec, topk_streaming, \
        unsketch
    from repro.kernels.csvec_topk import csvec_topk
    from repro.kernels.ref import csvec_topk_ref

    key = jax.random.PRNGKey(1)
    dim, rows, cols, k, chunk = 200_000, 5, 2048, 256, 16384
    cs = insert(make_csvec(key, dim=dim, rows=rows, cols=cols),
                jax.random.normal(jax.random.fold_in(key, 2),
                                  (dim,)) ** 3)
    want_v, want_i = csvec_topk_ref(cs.table, cs.params, dim, k)
    got_v, got_i = topk_streaming(cs, k, chunk=chunk)
    exact = bool((got_i == want_i).all()) and bool((got_v == want_v).all())
    ker_v, ker_i = csvec_topk(cs.table, cs.params, dim=dim, k=k,
                              chunk=chunk)
    kernel_exact = bool((ker_i == want_i).all())

    us_s = _timeit(lambda t: topk_streaming(
        type(cs)(table=t, params=cs.params, dim=dim), k, chunk=chunk),
        cs.table)
    us_d = _timeit(lambda t: unsketch(
        type(cs)(table=t, params=cs.params, dim=dim), k), cs.table)
    # peak intermediate: streaming O(r*chunk), dense O(r*dim)
    return [
        ("streaming_topk", f"bit_exact={exact}", f"us={us_s:.0f}",
         f"peak_elems~{rows * chunk}"),
        ("dense_unsketch", "oracle", f"us={us_d:.0f}",
         f"peak_elems~{rows * dim}"),
        ("pallas_csvec_topk", f"candidates_exact={kernel_exact}",
         "interpret", f"chunk={chunk}"),
    ]


def bench_wire(num_params: int, ccfg, tcfg):
    import dataclasses

    from repro.optim.compression import compressed_bytes

    dense = num_params * 4
    cs_bytes = compressed_bytes(num_params, ccfg)
    tk_bytes = compressed_bytes(num_params, tcfg)
    i8cfg = dataclasses.replace(ccfg, wire_dtype="int8")
    i8_bytes = compressed_bytes(num_params, i8cfg)
    rows = [
        ("dense_psum", dense, 1.0, "scales with D and W"),
        ("topk", tk_bytes, tk_bytes / dense,
         "indices+values; NOT mergeable under psum"),
        ("countsketch", cs_bytes, cs_bytes / dense,
         "r*c f32 table; exact psum merge, W-invariant"),
        ("countsketch_int8", i8_bytes, i8_bytes / dense,
         "r*c int8 + r f32 scales; residual stays in error feedback"),
    ]
    assert cs_bytes <= 0.10 * dense, (
        f"countsketch wire bytes {cs_bytes} exceed 10% of dense {dense}")
    assert i8_bytes <= 0.025 * dense, (
        f"int8 countsketch wire bytes {i8_bytes} exceed 2.5% of dense "
        f"{dense}")
    return rows


def _train(cfg, run, steps):
    from repro.data.synthetic import lm_batch
    from repro.train.state import init_train_state
    from repro.train.step import make_train_step

    key = jax.random.PRNGKey(0)
    state = init_train_state(key, cfg, run)
    step = jax.jit(make_train_step(cfg, run))
    losses = []
    for s in range(steps):
        tokens, labels = lm_batch(jax.random.fold_in(key, s),
                                  run.global_batch, run.seq_len,
                                  cfg.vocab_size)
        state, m = step(state, {"tokens": tokens, "labels": labels})
        losses.append(float(m["loss"]))
    return losses


def bench_convergence(ccfg, tcfg):
    import dataclasses

    from repro.configs import get_arch, reduced
    from repro.models.transformer import SketchSettings
    from repro.train.state import RunConfig

    cfg = reduced(get_arch("tinyllama-1.1b"))
    base = RunConfig(seq_len=32, global_batch=8,
                     sketch=SketchSettings(enabled=False),
                     warmup_steps=5, total_steps=STEPS)
    out = {}
    for name, comp in (("dense", None), ("topk", tcfg),
                       ("countsketch", ccfg)):
        run = dataclasses.replace(base, compression=comp)
        losses = _train(cfg, run, STEPS)
        out[name] = sum(losses[-LAST:]) / LAST
    return out


def _run_sub(code: str, n_devices: int = 4, timeout: int = 900):
    """Run a benchmark snippet in a subprocess with its own fake-device
    XLA_FLAGS (the parent already initialized jax with 1 device). The
    child counts collectives and wire bytes on fake CPU devices, so it
    is pinned to the CPU: it must never reach for a chip the parent
    holds."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-4000:])
    return [l for l in out.stdout.splitlines() if l.startswith("ROW,")]


def bench_collectives():
    """Per-collective wall time on a real W=4 shard_map mesh: the dense
    O(D) gradient pmean the sketch replaces, the O(r*c) table psum, and
    the O(p2*k) second-round value psum."""
    rows = _run_sub("""
        import time
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()), ("data",))
        D, r, c, p2k = 1_000_000, 5, 2048, 512

        def timed(fn, x, n=20):
            f = jax.jit(shard_map(fn, mesh=mesh, in_specs=P(),
                                  out_specs=P(), check_vma=False))
            jax.block_until_ready(f(x))
            t0 = time.perf_counter()
            for _ in range(n):
                jax.block_until_ready(f(x))
            return (time.perf_counter() - t0) / n * 1e6

        g = jax.random.normal(jax.random.PRNGKey(0), (D,))
        tab = jax.random.normal(jax.random.PRNGKey(1), (r, c))
        vals = jax.random.normal(jax.random.PRNGKey(2), (p2k,))
        us_d = timed(lambda x: jax.lax.pmean(x, "data"), g)
        us_t = timed(lambda x: jax.lax.psum(x, "data"), tab)
        us_p = timed(lambda x: jax.lax.psum(x, "data"), vals)
        print(f"ROW,dense_grad_pmean,{us_d:.0f}us,{D * 4}B W=4")
        print(f"ROW,sketch_table_psum,{us_t:.0f}us,{r * c * 4}B W=4")
        print(f"ROW,p2_value_psum,{us_p:.0f}us,{p2k * 4}B W=4")

        # ISSUE 4: the fused layout — every per-node (d, k) sketch
        # increment of an L-layer tree PLUS the table in ONE flat psum,
        # vs the per-node psums it replaces (3L+1 collectives). The
        # trace-time accounting hook independently reports the
        # collective counts.
        from repro.parallel.collectives import (
            collective_trace, psum_flat_segments)
        L, d, k = 12, 512, 33
        key = jax.random.PRNGKey(3)
        tree = {"ffn_in": {a: jax.random.normal(
                    jax.random.fold_in(key, i), (L, d, k))
                for i, a in enumerate("xyz")},
                "cs_table": tab}

        def per_node(t):
            return jax.tree.map(lambda x: jax.lax.psum(x, "data"), t)

        def fused(t):
            return psum_flat_segments(t, "data")

        with collective_trace() as log_f:
            jax.jit(shard_map(fused, mesh=mesh, in_specs=P(),
                              out_specs=P(), check_vma=False)
                    ).lower(tree)
        us_n = timed(per_node, tree)
        us_f = timed(fused, tree)
        nbytes = sum(e["bytes"] for e in log_f)
        print(f"ROW,per_node_psums_3L+1,{us_n:.0f}us,"
              f"{3 * L + 1} collectives W=4")
        print(f"ROW,fused_flat_psum,{us_f:.0f}us,"
              f"{len(log_f)} collective {nbytes}B W=4")
        print(f"ROW,fused_collective_count,{len(log_f)},"
              "trace-time accounting")
        assert len(log_f) == 1, log_f
    """)
    return [tuple(r.split(",")[1:]) for r in rows]


def bench_w4_gate():
    """ISSUE 2 acceptance: W=4 shard_map LM training, countsketch + p2
    vs the dense-pmean DP baseline — matched final loss at <= 10% of
    the dense wire bytes."""
    rows = _run_sub(f"""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_arch, reduced
        from repro.data.synthetic import lm_batch
        from repro.models.transformer import SketchSettings
        from repro.optim.compression import (
            CompressionConfig, compressed_bytes)
        from repro.optim.sketched_sgd import flat_dim
        from repro.train.state import RunConfig, init_train_state
        from repro.train.step import make_dp_train_step

        STEPS, LAST = {W4_STEPS}, {LAST}
        mesh = Mesh(np.array(jax.devices()), ("data",))
        cfg = reduced(get_arch("tinyllama-1.1b"))
        ccfg = CompressionConfig(mode="countsketch", cs_rows=5,
                                 cs_cols=1024, cs_k=2048,
                                 cs_momentum=0.0, cs_p2=2)
        base = RunConfig(seq_len=32, global_batch=8,
                         sketch=SketchSettings(enabled=False),
                         warmup_steps=5, total_steps=STEPS,
                         dp_axis_name="data")
        key = jax.random.PRNGKey(0)
        finals = {{}}
        for name, comp in (("dense", None), ("countsketch_p2", ccfg)):
            run = dataclasses.replace(base, compression=comp)
            state = init_train_state(key, cfg, run)
            state = jax.device_put(state, NamedSharding(mesh, P()))
            step = jax.jit(make_dp_train_step(cfg, run, mesh))
            losses = []
            for s in range(STEPS):
                tok, lab = lm_batch(jax.random.fold_in(key, s), 8, 32,
                                    cfg.vocab_size)
                state, m = step(state, {{"tokens": tok, "labels": lab}})
                losses.append(float(m["loss"]))
            finals[name] = sum(losses[-LAST:]) / LAST
            d = flat_dim(state.params)
        dense_b = d * 4
        cs_b = compressed_bytes(d, ccfg)
        ratio = cs_b / dense_b
        gap = abs(finals["countsketch_p2"] - finals["dense"])
        print(f"ROW,final_loss_dense_w4,{{finals['dense']:.4f}},"
              f"{{STEPS}} steps")
        print(f"ROW,final_loss_countsketch_p2_w4,"
              f"{{finals['countsketch_p2']:.4f}},{{STEPS}} steps")
        print(f"ROW,w4_wire_ratio,{{ratio:.4f}},{{cs_b}}B vs "
              f"{{dense_b}}B per step per worker")
        print(f"ROW,w4_loss_gap,{{gap:.4f}},tolerance={TOL}")
        assert ratio <= 0.10, (cs_b, dense_b)
        assert gap <= {TOL}, finals
        print("ROW,w4_gate,PASS,p2 exchange on; wire<=10% dense at "
              "matched loss")
    """)
    return [tuple(r.split(",")[1:]) for r in rows]


def bench_int8_gate():
    """ISSUE 4 acceptance: the FUSED one-collective W=4 step with the
    int8 count-sketch wire must match the dense-pmean W=4 run's final
    loss within 0.05 at <= 2.5% of its wire bytes — and its compiled
    HLO must contain exactly ONE collective per step (cs_p2=0; the p2
    round is the one documented second collective, see I8_STEPS)."""
    rows = _run_sub(f"""
        import dataclasses, re
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_arch, reduced
        from repro.data.synthetic import lm_batch
        from repro.models.transformer import SketchSettings
        from repro.optim.compression import (
            CompressionConfig, compressed_bytes)
        from repro.optim.sketched_sgd import flat_dim
        from repro.train.state import RunConfig, init_train_state
        from repro.train.step import make_dp_train_step

        STEPS, LAST = {I8_STEPS}, {LAST}
        mesh = Mesh(np.array(jax.devices()), ("data",))
        cfg = reduced(get_arch("tinyllama-1.1b"))
        ccfg = CompressionConfig(mode="countsketch", cs_rows=5,
                                 cs_cols=2048, cs_k=2048,
                                 cs_momentum=0.0, cs_p2=0,
                                 wire_dtype="int8")
        base = RunConfig(seq_len=32, global_batch=8,
                         sketch=SketchSettings(enabled=False),
                         warmup_steps=5, total_steps=STEPS,
                         dp_axis_name="data", dp_collective="fused")
        key = jax.random.PRNGKey(0)
        finals = {{}}
        for name, comp in (("dense", None), ("countsketch_int8", ccfg)):
            run = dataclasses.replace(base, compression=comp)
            state = init_train_state(key, cfg, run)
            state = jax.device_put(state, NamedSharding(mesh, P()))
            step = jax.jit(make_dp_train_step(cfg, run, mesh))
            losses = []
            for s in range(STEPS):
                tok, lab = lm_batch(jax.random.fold_in(key, s), 8, 32,
                                    cfg.vocab_size)
                state, m = step(state, {{"tokens": tok, "labels": lab}})
                losses.append(float(m["loss"]))
            finals[name] = sum(losses[-LAST:]) / LAST
            d = flat_dim(state.params)

        # collective count: exactly ONE all-reduce in the fused HLO
        run = dataclasses.replace(base, compression=ccfg)
        state = init_train_state(key, cfg, run)
        tok, lab = lm_batch(key, 8, 32, cfg.vocab_size)
        txt = jax.jit(make_dp_train_step(cfg, run, mesh)).lower(
            jax.device_put(state, NamedSharding(mesh, P())),
            {{"tokens": tok, "labels": lab}}).compile().as_text()
        colls = re.findall(
            r"= \\S+ (all-reduce|all-gather|reduce-scatter|"
            r"all-to-all|collective-permute)", txt)

        dense_b = d * 4
        cs_b = compressed_bytes(d, ccfg)
        ratio = cs_b / dense_b
        gap = abs(finals["countsketch_int8"] - finals["dense"])
        print(f"ROW,final_loss_dense_w4,{{finals['dense']:.4f}},"
              f"{{STEPS}} steps")
        print(f"ROW,final_loss_countsketch_int8_w4,"
              f"{{finals['countsketch_int8']:.4f}},{{STEPS}} steps")
        print(f"ROW,int8_wire_ratio,{{ratio:.4f}},{{cs_b}}B vs "
              f"{{dense_b}}B per step per worker")
        print(f"ROW,int8_loss_gap,{{gap:.4f}},tolerance=0.05")
        print(f"ROW,collectives_per_step,{{len(colls)}},{{colls}}")
        assert ratio <= 0.025, (cs_b, dense_b)
        assert gap <= 0.05, finals
        assert len(colls) == 1 and colls[0] == "all-reduce", colls
        print("ROW,int8_gate,PASS,one collective/step; int8 wire<=2.5% "
              "dense at loss gap<=0.05")
    """)
    return [tuple(r.split(",")[1:]) for r in rows]


def bench_overlap_gate():
    """ISSUE 5 acceptance: the overlap two-phase W=4 step with sketched
    BACKPROP trees (current-step DP-exact consumption, no lag) and the
    int8 count-sketch wire. Gate: int8 wire bytes <= 2.5% of dense at a
    loss gap <= 0.05 vs the dense-wire overlap run, with exactly TWO
    all-reduces per compiled step — the sketch psum first (it is the
    smaller, increment-sized buffer; the differential tier additionally
    asserts its schedule against the backward)."""
    rows = _run_sub(f"""
        import dataclasses, re
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_arch, reduced
        from repro.data.synthetic import lm_batch
        from repro.models.transformer import SketchSettings
        from repro.optim.compression import (
            CompressionConfig, compressed_bytes)
        from repro.optim.sketched_sgd import flat_dim
        from repro.sketches import tree_wire_spec
        from repro.train.state import RunConfig, init_train_state
        from repro.train.step import make_dp_train_step

        STEPS, LAST = {I8_STEPS}, {LAST}
        mesh = Mesh(np.array(jax.devices()), ("data",))
        cfg = reduced(get_arch("tinyllama-1.1b"))   # sketch_mode=backprop
        i8 = CompressionConfig(mode="countsketch", cs_rows=5,
                               cs_cols=2048, cs_k=2048,
                               cs_momentum=0.0, cs_p2=0,
                               wire_dtype="int8")
        mk = lambda comp: RunConfig(
            seq_len=16, global_batch=8, warmup_steps=5,
            total_steps=STEPS, dp_axis_name="data", dp_workers=4,
            dp_collective="overlap", compression=comp,
            sketch=SketchSettings(enabled=True, k_max=9, beta=0.9,
                                  recon_mode="fast"))
        key = jax.random.PRNGKey(0)
        finals = {{}}
        for name, comp in (("dense", None), ("int8", i8)):
            run = mk(comp)
            state = init_train_state(key, cfg, run)
            state = jax.device_put(state, NamedSharding(mesh, P()))
            step = jax.jit(make_dp_train_step(cfg, run, mesh))
            losses = []
            for s in range(STEPS):
                tok, lab = lm_batch(jax.random.fold_in(key, s), 8, 16,
                                    cfg.vocab_size)
                state, m = step(state, {{"tokens": tok,
                                         "labels": lab}})
                losses.append(float(m["loss"]))
            assert all(np.isfinite(losses))
            finals[name] = sum(losses[-LAST:]) / LAST
            d = flat_dim(state.params)

        # exactly TWO all-reduces, sketch psum (increment-sized) first
        run = mk(i8)
        state = init_train_state(key, cfg, run)
        early_total = tree_wire_spec(state.sketch).total
        tok, lab = lm_batch(key, 8, 16, cfg.vocab_size)
        txt = jax.jit(make_dp_train_step(cfg, run, mesh)).lower(
            jax.device_put(state, NamedSharding(mesh, P())),
            {{"tokens": tok, "labels": lab}}).compile().as_text()
        colls = re.findall(
            r"= \\S+ (all-reduce|all-gather|reduce-scatter|"
            r"all-to-all|collective-permute)", txt)
        entry = txt[txt.index("ENTRY"):]
        sizes = [int(m.group(1)) for m in re.finditer(
            r"= f32\\[(\\d+)\\]\\S* all-reduce\\(", entry)]

        dense_b = d * 4
        cs_b = compressed_bytes(d, i8)
        ratio = cs_b / dense_b
        gap = abs(finals["int8"] - finals["dense"])
        print(f"ROW,final_loss_dense_overlap_w4,"
              f"{{finals['dense']:.4f}},{{STEPS}} steps backprop trees")
        print(f"ROW,final_loss_int8_overlap_w4,"
              f"{{finals['int8']:.4f}},{{STEPS}} steps backprop trees")
        print(f"ROW,overlap_int8_wire_ratio,{{ratio:.4f}},{{cs_b}}B vs "
              f"{{dense_b}}B per step per worker")
        print(f"ROW,overlap_int8_loss_gap,{{gap:.4f}},tolerance=0.05")
        print(f"ROW,overlap_collectives_per_step,{{len(colls)}},"
              f"{{colls}} sizes={{sizes}}")
        assert ratio <= 0.025, (cs_b, dense_b)
        assert gap <= 0.05, finals
        assert len(colls) == 2 and set(colls) == {{"all-reduce"}}, colls
        # early = the increment buffer; late = table + 3 scalars + n
        late_total = i8.cs_rows * i8.cs_cols + 4
        assert sizes == [early_total, late_total], \\
            (sizes, early_total, late_total)
        print("ROW,overlap_gate,PASS,two collectives/step (sketch psum "
              "first); int8 wire<=2.5% dense at loss gap<=0.05 with "
              "NO consumption lag")
    """)
    return [tuple(r.split(",")[1:]) for r in rows]


def bench_int8_e2e_gate():
    """ISSUE 9 acceptance: EVERY non-counter cross-worker byte int8 and
    no serial third collective. The reduced archs are too narrow for
    the 1% gate to be meaningful (the per-row f32 scales dominate a
    k_max-wide row; increments scale linearly in d_model while dense
    grads scale quadratically), so this section widens the reduced
    tinyllama to d_model=256 — still CPU-trainable — where the ratio
    measures the regime the wire format was built for. Gate: total
    per-step wire (int8 increments + int8 table + f32 p2 values)
    <= 1% of the dense gradient psum, loss gap <= 0.05 vs the f32
    wire over the run, exactly TWO all-reduces in the fused HLO with
    cs_p2 > 0 (flat wire + the p2 round overlapped with the zero-grad
    dense optimizer pass — the serial layout's third collective is
    gone, not hidden in extra traffic)."""
    rows = _run_sub(f"""
        import dataclasses, re
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_arch, reduced
        from repro.data.synthetic import lm_batch
        from repro.models.transformer import SketchSettings
        from repro.optim.compression import (
            CompressionConfig, compressed_bytes)
        from repro.optim.sketched_sgd import flat_dim
        from repro.sketches import tree_wire_spec
        from repro.sketches.wire import int8_segment_bytes
        from repro.train.state import RunConfig, init_train_state
        from repro.train.step import collective_plan, make_dp_train_step

        STEPS, LAST = 8, 3
        mesh = Mesh(np.array(jax.devices()), ("data",))
        cfg = dataclasses.replace(
            reduced(get_arch("tinyllama-1.1b")), d_model=256, d_ff=512,
            num_heads=4, head_dim=64, vocab_size=512)
        ccfg = lambda wd: CompressionConfig(
            mode="countsketch", cs_rows=5, cs_cols=1024, cs_k=512,
            cs_momentum=0.0, cs_p2=2, wire_dtype=wd)
        mk = lambda wd: RunConfig(
            seq_len=16, global_batch=8, warmup_steps=3,
            total_steps=STEPS, dp_axis_name="data", dp_workers=4,
            dp_collective="fused", compression=ccfg(wd),
            sketch_wire_dtype=wd, p2_overlap=True,
            sketch=SketchSettings(enabled=True, k_max=5, beta=0.9,
                                  recon_mode="fast"))
        key = jax.random.PRNGKey(0)
        finals = {{}}
        for wd in ("fp32", "int8"):
            run = mk(wd)
            state = init_train_state(key, cfg, run)
            state = jax.device_put(state, NamedSharding(mesh, P()))
            step = jax.jit(make_dp_train_step(cfg, run, mesh))
            losses = []
            for s in range(STEPS):
                tok, lab = lm_batch(jax.random.fold_in(key, s), 8, 16,
                                    cfg.vocab_size)
                state, m = step(state, {{"tokens": tok,
                                         "labels": lab}})
                losses.append(float(m["loss"]))
            assert all(np.isfinite(losses))
            finals[wd] = sum(losses[-LAST:]) / LAST
            d = flat_dim(state.params)
            spec = tree_wire_spec(state.sketch)

        # total int8 wire: increment segments + table + p2 values —
        # the same closed forms the trace-time accounting hook uses
        run = mk("int8")
        dense_b = d * 4
        e2e_b = int8_segment_bytes(spec) + compressed_bytes(
            d, run.compression)
        ratio = e2e_b / dense_b
        gap = abs(finals["int8"] - finals["fp32"])

        # zero serial third collective: cs_p2 > 0 yet the fused HLO
        # holds exactly TWO all-reduces, with the plan recording the
        # p2/optimizer overlap (bitwise vs serial is the differential
        # tier's assert)
        state = init_train_state(key, cfg, run)
        tok, lab = lm_batch(key, 8, 16, cfg.vocab_size)
        txt = jax.jit(make_dp_train_step(cfg, run, mesh)).lower(
            jax.device_put(state, NamedSharding(mesh, P())),
            {{"tokens": tok, "labels": lab}}).compile().as_text()
        colls = re.findall(
            r"= \\S+ (all-reduce|all-gather|reduce-scatter|"
            r"all-to-all|collective-permute)", txt)
        plan = collective_plan(cfg, run, mesh_shape=dict(mesh.shape))

        print(f"ROW,final_loss_fp32_e2e_w4,{{finals['fp32']:.4f}},"
              f"{{STEPS}} steps d_model=256")
        print(f"ROW,final_loss_int8_e2e_w4,{{finals['int8']:.4f}},"
              f"{{STEPS}} steps d_model=256")
        print(f"ROW,int8_e2e_wire_ratio,{{ratio:.4f}},{{e2e_b}}B vs "
              f"{{dense_b}}B per step per worker")
        print(f"ROW,int8_e2e_loss_gap,{{gap:.4f}},tolerance=0.05")
        print(f"ROW,int8_e2e_collectives_per_step,{{len(colls)}},"
              f"{{colls}} with cs_p2=2 overlapped")
        assert ratio <= 0.01, (e2e_b, dense_b)
        assert gap <= 0.05, finals
        assert len(colls) == 2 and set(colls) == {{"all-reduce"}}, colls
        assert plan["p2_overlap"] is True and \\
            plan["sketch_wire_dtype"] == "int8", plan
        print("ROW,int8_e2e_gate,PASS,total wire<=1% dense at loss "
              "gap<=0.05; p2 overlapped — no serial third collective")
    """, timeout=1200)
    return [tuple(r.split(",")[1:]) for r in rows]


def bench_moe_gate():
    """ISSUE 10 acceptance: the MoE family's per-expert sketch nodes
    under W=4 DP. The (L, E, d, k) expert stacks stay per-expert-linear,
    so the overlap two-phase merge is BITWISE the per_node psum (qwen3-
    moe CONSUMES attn_o, so overlap — not fused — is the bitwise layout;
    fused keeps the documented one-step consumption lag). The plan
    numbers come from `collective_plan`'s registry-spec accounting
    (NodeSpec stack entries, not the dense group x layer product)."""
    rows = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_arch, reduced
        from repro.data.synthetic import lm_batch
        from repro.models.transformer import SketchSettings
        from repro.train.state import RunConfig, init_train_state
        from repro.train.step import collective_plan, make_dp_train_step

        mesh = Mesh(np.array(jax.devices()), ("data",))
        cfg = reduced(get_arch("qwen3-moe-30b-a3b"))
        key = jax.random.PRNGKey(0)
        states = {}
        for mode in ("per_node", "overlap", "fused"):
            run = RunConfig(seq_len=16, global_batch=8,
                            dp_axis_name="data", dp_workers=4,
                            dp_collective=mode,
                            warmup_steps=1, total_steps=40,
                            sketch=SketchSettings(enabled=True, k_max=9,
                                                  beta=0.9,
                                                  recon_mode="fast"))
            state = init_train_state(key, cfg, run)
            state = jax.device_put(state, NamedSharding(mesh, P()))
            step = jax.jit(make_dp_train_step(cfg, run, mesh))
            for s in range(3):
                tok, lab = lm_batch(jax.random.fold_in(key, s), 8, 16,
                                    cfg.vocab_size)
                state, m = step(state, {"tokens": tok, "labels": lab})
            states[mode] = (state, m, run)
        for a, b in zip(jax.tree.leaves(states["per_node"][0].sketch),
                        jax.tree.leaves(states["overlap"][0].sketch)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \\
                "MoE sketch trees diverged across DP layouts"
        gap = abs(float(states["per_node"][1]["loss"]) -
                  float(states["overlap"][1]["loss"]))
        lag = abs(float(states["per_node"][1]["loss"]) -
                  float(states["fused"][1]["loss"]))
        plan_p = collective_plan(cfg, states["per_node"][2])
        plan_o = collective_plan(cfg, states["overlap"][2])
        print(f"ROW,moe_fused_collectives,"
              f"{collective_plan(cfg, states['fused'][2])['collectives']},"
              f"one flat psum for the whole expert stack")
        print(f"ROW,moe_per_node_collectives,{plan_p['collectives']},"
              f"one per stack entry (experts x layers) + grads")
        print(f"ROW,moe_overlap_wire_bytes,{plan_o['wire_bytes']},"
              f"registry-spec accounting incl (L,E,d,k) stacks")
        print(f"ROW,moe_loss_gap,{gap:.6f},"
              f"overlap vs per_node after 3 steps (bitwise trees)")
        print(f"ROW,moe_fused_lag_gap,{lag:.6f},"
              f"fused one-step consumption lag, tolerance 0.05")
        assert plan_o["collectives"] < plan_p["collectives"]
        assert gap == 0.0, gap
        assert lag <= 0.05, lag
        print("ROW,moe_gate,PASS,per-expert nodes bitwise under the "
              "overlap merge; fused lag within tolerance")
    """)
    return [tuple(r.split(",")[1:]) for r in rows]


def bench_mesh_gate():
    """ISSUE 7 acceptance, structural half. No training and no
    subprocess — `collective_plan` is the same trace-free accounting the
    W=8 differential tier asserts against compiled HLO, and the memory
    side reuses the closed-form that `bench_memory_complexity` proves
    equal to a live shard. Gated metrics: dp-supergroup collective
    count (3: RS + AG + wire AR), model-axis step-issued collectives
    (0), the rs wire overhead over the fused single-psum layout (the
    sketch payload crosses the wire twice), and the W=8 per-worker
    sketch-state ratio."""
    import jax

    from repro.configs import get_arch, reduced
    from repro.models.transformer import SketchSettings
    from repro.sketches import (
        tree_memory_bytes, tree_memory_bytes_per_worker,
    )
    from repro.train.state import RunConfig, init_train_state
    from repro.train.step import collective_plan

    cfg = reduced(get_arch("tinyllama-1.1b"))
    sk = SketchSettings(enabled=True, k_max=9)
    mesh_shape = {"pod": 2, "data": 2, "model": 2}
    rs = RunConfig(seq_len=16, global_batch=8, sketch=sk, dp_workers=4,
                   dp_axis_name=("pod", "data"), dp_collective="overlap",
                   dp_merge="reduce_scatter")
    fused = RunConfig(seq_len=16, global_batch=8, sketch=sk,
                      dp_workers=4, dp_axis_name="data",
                      dp_collective="fused")
    plan = collective_plan(cfg, rs, mesh_shape=mesh_shape)
    fplan = collective_plan(cfg, fused)
    assert plan["layout"] == "rs_overlap", plan
    assert plan["by_kind"] == {"all_reduce": 1, "reduce_scatter": 1,
                               "all_gather": 1}, plan
    assert plan["per_axis"] == {"pod+data": 3, "model": 0}, plan
    overhead = plan["wire_bytes"] / fplan["wire_bytes"]

    run = RunConfig(seq_len=16, global_batch=4, sketch=sk)
    tree = init_train_state(jax.random.PRNGKey(0), cfg, run).sketch
    full = tree_memory_bytes(tree)
    ratios = {w: tree_memory_bytes_per_worker(tree, dp_shards=w) / full
              for w in (1, 2, 4, 8)}
    assert ratios[1] == 1.0 and ratios[8] < ratios[4] < ratios[2], ratios
    assert ratios[8] <= 0.30, ratios   # 1/8 tile + replicated psi/proj

    rows = [
        ("rs_dp_collectives", plan["per_axis"]["pod+data"],
         "RS+AG+AR on the flattened (pod,data) supergroup"),
        ("rs_model_axis_collectives", plan["per_axis"]["model"],
         "zero step-issued TP collectives"),
        ("rs_wire_overhead_vs_fused", f"{overhead:.4f}",
         f"{plan['wire_bytes']}B vs {fplan['wire_bytes']}B; sketch "
         "crosses the wire twice (RS down + AG back)"),
        ("per_worker_mem_ratio_w8", f"{ratios[8]:.4f}",
         f"{tree_memory_bytes_per_worker(tree, dp_shards=8)}B of "
         f"{full}B replicated"),
        ("mesh_gate", "PASS",
         "rs merge: 3 dp-supergroup collectives, 0 model-axis; W=8 "
         "worker holds <=30% of the replicated sketch state"),
    ]
    return [(n, str(v), note) for n, v, note in rows]


def _rows_value(rows, name):
    for row in rows:
        if row[0] == name:
            return float(row[1])
    raise KeyError(f"bench row {name!r} not emitted")


# Metrics gated RELATIVELY against the committed baseline: wire ratios
# and collective counts — the two quantities the collective layouts
# exist to hold down. Loss gaps stay ABSOLUTE gates (asserted in their
# sections): a baseline captured on a lucky seed must not ratchet them.
RELATIVE_GATES = (
    "wire_ratio_countsketch",
    "wire_ratio_countsketch_int8",
    "collectives_fused_flat_psum",
    "w4_wire_ratio",
    "int8_wire_ratio",
    "int8_collectives_per_step",
    "overlap_int8_wire_ratio",
    "overlap_collectives_per_step",
    "int8_e2e_wire_ratio",
    "int8_e2e_collectives_per_step",
    "mesh_rs_dp_collectives",
    "mesh_rs_model_axis_collectives",
    "mesh_rs_wire_overhead",
    "mesh_per_worker_mem_ratio_w8",
    "moe_fused_collectives",
    "moe_overlap_wire_bytes",
)
REGRESSION_TOL = 0.10


def check_baseline(metrics: dict, baseline_path: str,
                   gates: tuple = RELATIVE_GATES,
                   tol: float = REGRESSION_TOL) -> list[str]:
    """Compare the relative-gated metrics against the committed
    baseline, ASYMMETRICALLY (ISSUE 9): >tol above baseline FAILS;
    >tol BELOW baseline only WARNS that the committed baseline is
    stale and should be refreshed — an improvement (a new wire format
    shrinking a ratio, a layout dropping a collective) must land
    without hand-editing BENCH_countsketch.json. Returns the failure
    list (empty == pass). Metrics absent from an older baseline are
    skipped (the next baseline refresh picks them up); metrics absent
    from the CURRENT run fail — a section silently dropping a gate is
    itself a regression.

    Shared across the BENCH_* suite (bench_serve.py gates its monitor
    overhead ratio through the same machinery with its own gate
    tuple)."""
    with open(baseline_path) as f:
        base = json.load(f)["metrics"]
    failures = []
    for key in gates:
        if key not in metrics:
            failures.append(f"{key}: missing from this run")
            continue
        if key not in base:
            print(f"baseline,{key},skipped,not in committed baseline")
            continue
        now, ref = metrics[key], base[key]
        limit = ref * (1.0 + tol)
        if now > limit:
            print(f"baseline,{key},FAIL,{now:.4f} vs baseline "
                  f"{ref:.4f} (limit {limit:.4f})")
            failures.append(
                f"{key}: {now:.4f} regressed >{tol:.0%} vs "
                f"baseline {ref:.4f}")
        elif now < ref * (1.0 - tol):
            print(f"baseline,{key},WARN-better,{now:.4f} improved "
                  f">{tol:.0%} on baseline {ref:.4f} — refresh the "
                  f"committed BENCH json to lock in the gain")
        else:
            print(f"baseline,{key},PASS,{now:.4f} vs baseline "
                  f"{ref:.4f} (limit {limit:.4f})")
    return failures


def write_bench_json(path: str, metrics: dict) -> None:
    """BENCH_*.json writer shared by the bench suite: schema tag +
    ``telemetry.run_metadata`` attribution header + the gated metrics —
    so every committed baseline records the commit/environment it was
    captured on (DESIGN.md §11)."""
    from repro.telemetry import run_metadata

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"schema": 1, "meta": run_metadata(),
                   "metrics": metrics}, f, indent=2, sort_keys=True)
        f.write("\n")


def main(argv=None):
    from repro.optim.compression import CompressionConfig
    from repro.optim.sketched_sgd import countsketch_wire_bytes

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable metrics (wire ratios, "
                         "loss gaps, collective counts) as JSON")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="committed BENCH_countsketch.json to gate "
                         "against (wire-ratio or collective-count "
                         "regression beyond 10%% fails)")
    args = ap.parse_args(argv)
    metrics: dict = {}

    ccfg = CompressionConfig(mode="countsketch", cs_rows=5,
                             cs_cols=2048, cs_k=2048, cs_momentum=0.0)
    tcfg = CompressionConfig(mode="topk", topk_frac=0.05)

    print("section,metric,value,notes")
    for row in bench_kernel():
        print(",".join(("kernel",) + row))
    for row in bench_streaming():
        print(",".join(("streaming",) + row))

    num_params = 106_816          # reduced tinyllama (the LM task below)
    for name, nbytes, ratio, note in bench_wire(num_params, ccfg, tcfg):
        print(f"wire,{name},{nbytes}B,ratio={ratio:.3f} ({note})")
        if name in ("countsketch", "countsketch_int8"):
            metrics[f"wire_ratio_{name}"] = ratio
    assert countsketch_wire_bytes(ccfg) == ccfg.cs_rows * ccfg.cs_cols * 4

    coll_rows = bench_collectives()
    for row in coll_rows:
        print(",".join(("collectives",) + row))
    metrics["collectives_fused_flat_psum"] = _rows_value(
        coll_rows, "fused_collective_count")

    finals = bench_convergence(ccfg, tcfg)
    for name, loss in finals.items():
        print(f"convergence,final_loss_{name},{loss:.4f},last{LAST}-avg "
              f"over {STEPS} steps")
    gap = abs(finals["countsketch"] - finals["dense"])
    print(f"convergence,cs_vs_dense_gap,{gap:.4f},tolerance={TOL}")
    metrics["convergence_cs_vs_dense_gap"] = gap
    assert gap <= TOL, (
        f"countsketch final loss {finals['countsketch']:.4f} not within "
        f"{TOL} of dense {finals['dense']:.4f}")
    print("convergence,gate,PASS,"
          f"bytes ratio {countsketch_wire_bytes(ccfg) / (num_params * 4):.3f}"
          " <= 0.10 at matched final loss")

    w4_rows = bench_w4_gate()
    for row in w4_rows:
        print(",".join(("w4",) + row))
    metrics["w4_wire_ratio"] = _rows_value(w4_rows, "w4_wire_ratio")
    metrics["w4_loss_gap"] = _rows_value(w4_rows, "w4_loss_gap")

    i8_rows = bench_int8_gate()
    for row in i8_rows:
        print(",".join(("int8",) + row))
    metrics["int8_wire_ratio"] = _rows_value(i8_rows, "int8_wire_ratio")
    metrics["int8_loss_gap"] = _rows_value(i8_rows, "int8_loss_gap")
    metrics["int8_collectives_per_step"] = _rows_value(
        i8_rows, "collectives_per_step")

    ov_rows = bench_overlap_gate()
    for row in ov_rows:
        print(",".join(("overlap",) + row))
    metrics["overlap_int8_wire_ratio"] = _rows_value(
        ov_rows, "overlap_int8_wire_ratio")
    metrics["overlap_int8_loss_gap"] = _rows_value(
        ov_rows, "overlap_int8_loss_gap")
    metrics["overlap_collectives_per_step"] = _rows_value(
        ov_rows, "overlap_collectives_per_step")

    e2e_rows = bench_int8_e2e_gate()
    for row in e2e_rows:
        print(",".join(("int8_e2e",) + row))
    metrics["int8_e2e_wire_ratio"] = _rows_value(
        e2e_rows, "int8_e2e_wire_ratio")
    metrics["int8_e2e_loss_gap"] = _rows_value(
        e2e_rows, "int8_e2e_loss_gap")
    metrics["int8_e2e_collectives_per_step"] = _rows_value(
        e2e_rows, "int8_e2e_collectives_per_step")

    mesh_rows = bench_mesh_gate()
    for row in mesh_rows:
        print(",".join(("mesh",) + row))
    metrics["mesh_rs_dp_collectives"] = _rows_value(
        mesh_rows, "rs_dp_collectives")
    metrics["mesh_rs_model_axis_collectives"] = _rows_value(
        mesh_rows, "rs_model_axis_collectives")
    metrics["mesh_rs_wire_overhead"] = _rows_value(
        mesh_rows, "rs_wire_overhead_vs_fused")
    metrics["mesh_per_worker_mem_ratio_w8"] = _rows_value(
        mesh_rows, "per_worker_mem_ratio_w8")

    moe_rows = bench_moe_gate()
    for row in moe_rows:
        print(",".join(("moe",) + row))
    metrics["moe_fused_collectives"] = _rows_value(
        moe_rows, "moe_fused_collectives")
    metrics["moe_overlap_wire_bytes"] = _rows_value(
        moe_rows, "moe_overlap_wire_bytes")
    metrics["moe_loss_gap"] = _rows_value(moe_rows, "moe_loss_gap")
    metrics["moe_fused_lag_gap"] = _rows_value(moe_rows, "moe_fused_lag_gap")

    if args.json:
        write_bench_json(args.json, metrics)
        print(f"json,written,{args.json},{len(metrics)} metrics")

    if args.baseline:
        failures = check_baseline(metrics, args.baseline)
        if failures:
            print("baseline,gate,FAIL," + "; ".join(failures))
            raise SystemExit(
                "bench regression vs committed baseline:\n  " +
                "\n  ".join(failures))
        print(f"baseline,gate,PASS,wire ratios + collective counts "
              f"within {REGRESSION_TOL:.0%} of {args.baseline}")


if __name__ == "__main__":
    main()
