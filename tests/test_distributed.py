"""Distribution correctness on 8 fake CPU devices (subprocess — the main
test process must keep seeing 1 device).

Covers: sharded train step runs for representative archs (dense, MoE-EP,
MoE-TP, ssm, hybrid); sharded == unsharded numerics; mini dry-run
(lower+compile) on a (2,2,2) pod mesh exercising the multi-pod axis.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["granite-34b", "qwen3-moe-30b-a3b",
                                  "mixtral-8x22b", "xlstm-1.3b",
                                  "recurrentgemma-2b"])
def test_sharded_step_matches_unsharded(arch):
    out = _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_arch, reduced
        from repro.launch.mesh import make_mesh, rules_for_mesh
        from repro.parallel.sharding import use_rules, param_shardings
        from repro.train.state import RunConfig, init_train_state
        from repro.train.step import make_train_step
        from repro.models.transformer import SketchSettings
        from repro.data.synthetic import lm_batch
        import dataclasses

        cfg = reduced(get_arch({arch!r}))
        if cfg.is_moe:   # avoid capacity-drop differences across layouts
            cfg = dataclasses.replace(cfg, capacity_factor=8.0)
        st = SketchSettings(enabled=True, k_max=9, beta=0.9,
                            recon_mode="fast")
        run = RunConfig(seq_len=32, global_batch=4, sketch=st)
        key = jax.random.PRNGKey(0)
        tokens, labels = lm_batch(key, 4, 32, cfg.vocab_size)
        batch = {{"tokens": tokens, "labels": labels}}

        # unsharded reference
        state0 = init_train_state(key, cfg, run)
        s_ref, m_ref = jax.jit(make_train_step(cfg, run))(state0, batch)

        mesh = make_mesh(2, 4)
        rules = rules_for_mesh(mesh)
        with use_rules(rules), mesh:
            state = init_train_state(key, cfg, run)
            state = jax.device_put(state, param_shardings(rules, state))
            s_sh, m_sh = jax.jit(make_train_step(cfg, run))(state, batch)
        dl = abs(float(m_ref["loss"]) - float(m_sh["loss"]))
        dg = abs(float(m_ref["grad_norm"]) - float(m_sh["grad_norm"]))
        print("DL", dl, "DG", dg)
        assert dl < 5e-2, (dl, float(m_ref['loss']), float(m_sh['loss']))
        assert dg < 5e-1, dg
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_dp_exact_sketch_matches_full_batch_w4():
    """DP-exact sketch semantics (ISSUE 3): under make_dp_train_step the
    per-token EMA increments are psum-ed INSIDE the forward. On CPU,
    psum sums the worker partials sequentially in rank order, so the
    W=4 sketch must be BITWISE equal to the single-worker full-batch
    sketch computed by accumulating the same per-shard increments in
    worker order (which, by linearity of the contraction, IS the
    full-batch sketch under the row-tiled projection)."""
    out = _run("""
        import dataclasses, functools
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.sketches import ema_triple_update

        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        W, Tl, d, k = 4, 16, 24, 9
        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, 6)
        a = jax.random.normal(ks[0], (W * Tl, d))
        ups, omg, phi = (jax.random.normal(ks[i], (Tl, k))
                         for i in (1, 2, 3))
        psi = jax.random.normal(ks[4], (k,))
        x0 = jnp.zeros((d, k))
        ka = jnp.asarray(7)
        beta = 0.9

        upd = functools.partial(
            ema_triple_update, upsilon=ups, omega=omg, phi=phi, psi=psi,
            beta=beta, k_active=ka)
        dp = jax.jit(shard_map(
            lambda sh: upd(x0, x0, x0, a=sh, axis_name="data"),
            mesh=mesh, in_specs=P("data"), out_specs=P(),
            check_vma=False))
        got = dp(a)

        # single-worker full-batch reference: per-shard increments
        # accumulated sequentially in worker order (x0 = 0 => the
        # update IS the increment)
        shards = a.reshape(W, Tl, d)
        ref = [jnp.zeros((d, k))] * 3
        for w in range(W):
            inc = upd(jnp.zeros((d, k)), jnp.zeros((d, k)),
                      jnp.zeros((d, k)), a=shards[w])
            ref = [r + i for r, i in zip(ref, inc)]
        for g, r in zip(got, ref):
            assert np.array_equal(np.asarray(g), np.asarray(r)), \\
                "psum-inside-forward is not bitwise full-batch"

        # cross-check against the one-matmul full-batch sketch with the
        # row-tiled projection (same reals, different fp summation)
        full = ema_triple_update(
            x0, x0, x0, a, jnp.tile(ups, (W, 1)), jnp.tile(omg, (W, 1)),
            jnp.tile(phi, (W, 1)), psi, beta, ka)
        for g, f in zip(got, full):
            np.testing.assert_allclose(np.asarray(g), np.asarray(f),
                                       atol=1e-5, rtol=1e-5)

        # end-to-end: the W=4 DP train step's sketch equals the sum of
        # the four per-shard forward increments (zero-initialized EMA)
        from repro.configs import get_arch, reduced
        from repro.models.transformer import SketchSettings, forward
        from repro.train.state import RunConfig, init_train_state
        from repro.train.step import make_dp_train_step
        from repro.data.synthetic import lm_batch

        cfg = reduced(get_arch("tinyllama-1.1b"))
        run = RunConfig(seq_len=16, global_batch=8, dp_axis_name="data",
                        dp_workers=4,
                        sketch=SketchSettings(enabled=True, k_max=9,
                                              beta=0.9,
                                              recon_mode="fast"))
        state = init_train_state(jax.random.PRNGKey(1), cfg, run)
        tokens, labels = lm_batch(jax.random.PRNGKey(2), 8, 16,
                                  cfg.vocab_size)
        dp_step = jax.jit(make_dp_train_step(cfg, run, mesh))
        new_state, metrics = dp_step(state, {"tokens": tokens,
                                             "labels": labels})

        want = jax.tree.map(jnp.zeros_like,
                            {n: (v.x, v.y, v.z)
                             for n, v in state.sketch.nodes.items()})
        for w in range(4):
            out = forward(state.params, tokens[2 * w: 2 * w + 2],
                          cfg=cfg, mode="train",
                          sketch_state=state.sketch,
                          settings=dataclasses.replace(run.sketch,
                                                       dp_axis=None))
            inc = {n: (v.x, v.y, v.z)
                   for n, v in out["sketch_state"].nodes.items()}
            want = jax.tree.map(lambda acc, i: acc + i, want, inc)
        got_nodes = {n: (v.x, v.y, v.z)
                     for n, v in new_state.sketch.nodes.items()}
        for a_, b_ in zip(jax.tree.leaves(got_nodes),
                          jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a_), np.asarray(b_),
                                       atol=5e-6, rtol=5e-6)
        assert bool(jnp.isfinite(metrics["loss"]))
        print("OK")
    """, devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_fsdp_strategy_matches_megatron():
    """The §Perf beyond-paper FSDP layout is numerically identical to the
    Megatron baseline (same math, different collectives)."""
    out = _run("""
        import jax
        from repro.configs import get_arch, reduced
        from repro.launch.mesh import make_mesh, rules_for_mesh
        from repro.parallel.sharding import use_rules, param_shardings
        from repro.train.state import RunConfig, init_train_state
        from repro.train.step import make_train_step
        from repro.models.transformer import SketchSettings
        from repro.data.synthetic import lm_batch

        cfg = reduced(get_arch("granite-34b"))
        run = RunConfig(seq_len=32, global_batch=4,
                        sketch=SketchSettings(enabled=True, k_max=9))
        key = jax.random.PRNGKey(0)
        tokens, labels = lm_batch(key, 4, 32, cfg.vocab_size)
        batch = {"tokens": tokens, "labels": labels}
        losses = []
        mesh = make_mesh(2, 4)
        for strat in ("megatron", "fsdp"):
            rules = rules_for_mesh(mesh, strategy=strat)
            with use_rules(rules), mesh:
                state = init_train_state(key, cfg, run)
                state = jax.device_put(
                    state, param_shardings(rules, state))
                _, m = jax.jit(make_train_step(cfg, run))(state, batch)
                losses.append(float(m["loss"]))
        assert abs(losses[0] - losses[1]) < 1e-4, losses
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_mini_multipod_dryrun_compiles():
    """(pod=2, data=2, model=2) mesh: lower + compile a reduced train
    step — proves the pod axis composes (full-scale version = launch/
    dryrun.py on 512 devices)."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_arch, reduced
        from repro.launch.mesh import make_mesh, rules_for_mesh
        from repro.parallel.sharding import use_rules, param_shardings
        from repro.train.state import RunConfig, abstract_train_state
        from repro.train.step import make_train_step
        from repro.models.transformer import SketchSettings
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = reduced(get_arch("gemma3-27b"))
        st = SketchSettings(enabled=True, k_max=9)
        run = RunConfig(seq_len=32, global_batch=8, sketch=st)
        mesh = make_mesh(2, 2, n_pod=2)
        rules = rules_for_mesh(mesh)
        with use_rules(rules), mesh:
            state = abstract_train_state(cfg, run)
            sh = param_shardings(rules, state)
            b = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
            bsh = {k: NamedSharding(mesh, P(("pod", "data"), None))
                   for k in b}
            lowered = jax.jit(make_train_step(cfg, run),
                              in_shardings=(sh, bsh)).lower(state, b)
            compiled = lowered.compile()
            print("coll-present:",
                  "all-reduce" in compiled.as_text() or
                  "all-gather" in compiled.as_text())
        print("OK")
    """)
    assert "OK" in out


# ---------------------------------------------------------------------------
# ISSUE 4 differential tier: the fused one-collective-per-step DP path
# vs the PR 3 per-node-psum reference
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fused_flat_psum_bitwise_parity_mlp_variant_trees():
    """W=4 differential parity at the sketch-subsystem level, one tree
    per MLP variant (sketched_fixed / sketched_adaptive / monitor as
    paper-kind trees at their distinct ranks+betas, corange as the
    ragged Tropp tree): packing every node's local increments into ONE
    flat psum and applying the merged result must be BITWISE identical
    to the PR 3 per-node `ema_triple_update(axis_name=...)` psums —
    and, for the corange kind, to per-leaf psums of its increments."""
    out = _run("""
        import dataclasses, functools
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.configs.paper import MLPConfig
        from repro.core.sketch import SketchConfig
        from repro.sketches import corange_triple_update, \\
            ema_triple_update, segment_spec, tree_increment_leaves
        from repro.sketches.update import ema_apply_increment, \\
            ema_triple_increment
        from repro.parallel.collectives import psum_flat_segments
        from repro.train.paper_trainer import init_mlp_sketch

        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        W, Tl = 4, 8

        def paper_tree(variant, rank, beta, seed):
            cfg = MLPConfig(name="t", d_in=20, d_hidden=28, d_out=4,
                            num_hidden_layers=3, activation="tanh",
                            batch_size=Tl, learning_rate=1e-3)
            scfg = SketchConfig(rank=rank, max_rank=4, beta=beta,
                                batch_size=Tl)
            sk = init_mlp_sketch(jax.random.PRNGKey(seed), cfg, scfg,
                                 variant)
            if variant != "corange":
                # nonzero state so the beta*x + inc accumulate is
                # exercised, not just the increment
                sk = dataclasses.replace(sk, nodes={
                    "hidden": dataclasses.replace(
                        sk.nodes["hidden"],
                        x=0.1 * sk.nodes["hidden"].psi[..., None, :] *
                        jnp.ones((28, 1)))})
            return cfg, scfg, sk

        variants = [("sketched_fixed", 3, 0.9, 0),
                    ("sketched_adaptive", 2, 0.9, 1),
                    ("monitor", 4, 0.95, 2),
                    ("corange", 3, 0.9, 3)]
        for variant, rank, beta, seed in variants:
            cfg, scfg, sk = paper_tree(variant, rank, beta, seed)
            node = sk.nodes["hidden"]
            L = cfg.num_hidden_layers
            ka = sk.k_active
            d = cfg.d_hidden
            acts = jax.random.normal(jax.random.PRNGKey(100 + seed),
                                     (L, W * Tl, d))

            if variant == "corange":
                # increments (zero-state update == pure increment),
                # per worker shard, per layer
                def incs(a_sh):   # a_sh (L, Tl, d)
                    ups = jax.vmap(lambda xc, yc, zc, a:
                                   corange_triple_update(
                                       xc, yc, zc, a, sk.proj,
                                       scfg.beta, ka))
                    return ups(jnp.zeros_like(node.x),
                               jnp.zeros_like(node.y),
                               jnp.zeros_like(node.z), a_sh)

                def fused(a_sh):
                    ix, iy, iz = incs(a_sh)
                    leaves = {"hidden": {"x": ix, "y": iy, "z": iz}}
                    return psum_flat_segments(leaves, "data")

                def per_leaf(a_sh):
                    ix, iy, iz = incs(a_sh)
                    pm = lambda t: jax.lax.psum(t, "data")
                    return {"hidden": {"x": pm(ix), "y": pm(iy),
                                       "z": pm(iz)}}

                sh = lambda f: jax.jit(shard_map(
                    lambda a: f(a.reshape(L, Tl, d)),
                    mesh=mesh, in_specs=P(None, "data"), out_specs=P(),
                    check_vma=False))
                got = sh(fused)(acts)
                want = sh(per_leaf)(acts)
                for g, w in zip(jax.tree.leaves(got),
                                jax.tree.leaves(want)):
                    assert np.array_equal(np.asarray(g), np.asarray(w))
                print("corange flat-psum bitwise OK")
                continue

            # paper-kind trees: full apply parity vs the PR 3 path
            def per_node(a_sh):   # a_sh (L, Tl, d)
                def one(l):
                    return ema_triple_update(
                        node.x[l], node.y[l], node.z[l], a_sh[l],
                        sk.proj["upsilon"], sk.proj["omega"],
                        sk.proj["phi"], node.psi[l], scfg.beta, ka,
                        axis_name="data")
                outs = [one(l) for l in range(L)]
                return {"hidden": {
                    "x": jnp.stack([o[0] for o in outs]),
                    "y": jnp.stack([o[1] for o in outs]),
                    "z": jnp.stack([o[2] for o in outs])}}

            def fused(a_sh):
                def one(l):
                    return ema_triple_increment(
                        node.x[l], node.y[l], node.z[l], a_sh[l],
                        sk.proj["upsilon"], sk.proj["omega"],
                        sk.proj["phi"], node.psi[l], scfg.beta, ka)
                outs = [one(l) for l in range(L)]
                leaves = {"hidden": {
                    "x": jnp.stack([o[0] for o in outs]),
                    "y": jnp.stack([o[1] for o in outs]),
                    "z": jnp.stack([o[2] for o in outs])}}
                merged = psum_flat_segments(leaves, "data")
                m = merged["hidden"]
                return {"hidden": {
                    "x": ema_apply_increment(node.x, m["x"], scfg.beta,
                                             ka),
                    "y": ema_apply_increment(node.y, m["y"], scfg.beta,
                                             ka),
                    "z": ema_apply_increment(node.z, m["z"], scfg.beta,
                                             ka)}}

            sh = lambda f: jax.jit(shard_map(
                lambda a: f(a.reshape(L, Tl, d)),
                mesh=mesh, in_specs=P(None, "data"), out_specs=P(),
                check_vma=False))
            got = sh(fused)(acts)
            want = sh(per_node)(acts)
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                assert np.array_equal(np.asarray(g), np.asarray(w)), \\
                    variant
            print(variant, "fused apply bitwise OK")
        print("OK")
    """, devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_fused_step_bitwise_vs_per_node_and_one_collective_w4():
    """E2E LM differential at W=4 (fp32 wire): with monitoring-only
    sketches (never consumed by the backward) the fused step must be
    BITWISE identical to the PR 3 per-node-psum step — full state AND
    metrics, over multiple steps, both on the dense grad wire and on
    the countsketch wire — while its compiled HLO contains exactly ONE
    collective (the flat-segment psum)."""
    out = _run("""
        import dataclasses, re
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_arch, reduced
        from repro.data.synthetic import lm_batch
        from repro.models.transformer import SketchSettings
        from repro.optim.compression import CompressionConfig
        from repro.train.state import RunConfig, init_train_state
        from repro.train.step import make_dp_train_step

        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        cfg = dataclasses.replace(reduced(get_arch("tinyllama-1.1b")),
                                  sketch_mode="monitor")
        ccfg = CompressionConfig(mode="countsketch", cs_rows=5,
                                 cs_cols=512, cs_k=256, cs_momentum=0.0)
        key = jax.random.PRNGKey(0)
        tokens, labels = lm_batch(jax.random.PRNGKey(2), 8, 16,
                                  cfg.vocab_size)
        batch = {"tokens": tokens, "labels": labels}

        for comp in (None, ccfg):
            states = {}
            for mode in ("per_node", "fused"):
                run = RunConfig(seq_len=16, global_batch=8,
                                dp_axis_name="data", dp_workers=4,
                                compression=comp, dp_collective=mode,
                                sketch=SketchSettings(
                                    enabled=True, k_max=9, beta=0.9,
                                    recon_mode="fast"))
                state = init_train_state(key, cfg, run)
                state = jax.device_put(state, NamedSharding(mesh, P()))
                step = jax.jit(make_dp_train_step(cfg, run, mesh))
                for _ in range(3):
                    state, m = step(state, batch)
                states[mode] = (state, m)
            a, b = states["per_node"], states["fused"]
            la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
            assert len(la) == len(lb)
            for x, y in zip(la, lb):
                assert np.array_equal(np.asarray(x), np.asarray(y)), \\
                    "fused step diverged from per_node"
            print("bitwise OK", "countsketch" if comp else "dense")

            # exactly ONE collective in the fused HLO
            run = RunConfig(seq_len=16, global_batch=8,
                            dp_axis_name="data", dp_workers=4,
                            compression=comp, dp_collective="fused",
                            sketch=SketchSettings(enabled=True, k_max=9,
                                                  beta=0.9,
                                                  recon_mode="fast"))
            state = init_train_state(key, cfg, run)
            txt = jax.jit(make_dp_train_step(cfg, run, mesh)).lower(
                jax.device_put(state, NamedSharding(mesh, P())),
                batch).compile().as_text()
            ops = re.findall(
                r"= \\S+ (all-reduce|all-gather|reduce-scatter|"
                r"all-to-all|collective-permute)", txt)
            assert len(ops) == 1 and ops[0] == "all-reduce", ops
            print("one-collective OK", "countsketch" if comp else
                  "dense")
        print("OK")
    """, devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_fused_step_int8_and_backprop_lag_loss_gap_w4():
    """The two documented approximations of the fused path stay inside
    the 0.05 loss-gap budget at W=4 on the synthetic LM task:

      * int8 wire (monitor sketches): quantization noise on the
        count-sketch table, absorbed by error feedback;
      * sketched-backprop consumption lag (fp32): sketched_matmul reads
        the previous step's merged triple instead of the current one.
    """
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_arch, reduced
        from repro.data.synthetic import lm_batch
        from repro.models.transformer import SketchSettings
        from repro.optim.compression import CompressionConfig
        from repro.train.state import RunConfig, init_train_state
        from repro.train.step import make_dp_train_step

        STEPS, LAST = 20, 5
        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        key = jax.random.PRNGKey(0)

        def train(cfg, run):
            state = init_train_state(key, cfg, run)
            state = jax.device_put(state, NamedSharding(mesh, P()))
            step = jax.jit(make_dp_train_step(cfg, run, mesh))
            losses = []
            for s in range(STEPS):
                tok, lab = lm_batch(jax.random.fold_in(key, s), 8, 16,
                                    cfg.vocab_size)
                state, m = step(state, {"tokens": tok, "labels": lab})
                losses.append(float(m["loss"]))
            assert all(np.isfinite(losses))
            return sum(losses[-LAST:]) / LAST

        # --- int8 wire vs fp32 wire (monitor sketches) ---------------
        cfg = dataclasses.replace(reduced(get_arch("tinyllama-1.1b")),
                                  sketch_mode="monitor")
        mk = lambda wd: RunConfig(
            seq_len=16, global_batch=8, dp_axis_name="data",
            dp_workers=4, warmup_steps=2, total_steps=STEPS,
            compression=CompressionConfig(
                mode="countsketch", cs_rows=5, cs_cols=512, cs_k=512,
                cs_momentum=0.0, wire_dtype=wd),
            sketch=SketchSettings(enabled=True, k_max=9, beta=0.9,
                                  recon_mode="fast"))
        f32, i8 = train(cfg, mk("fp32")), train(cfg, mk("int8"))
        gap = abs(i8 - f32)
        print(f"int8 gap {gap:.4f} (fp32 {f32:.4f} int8 {i8:.4f})")
        assert gap <= 0.05, (f32, i8)

        # --- consumption lag: fused vs per_node, backprop sketches ---
        cfg = reduced(get_arch("tinyllama-1.1b"))
        mk = lambda mode: RunConfig(
            seq_len=16, global_batch=8, dp_axis_name="data",
            dp_workers=4, warmup_steps=2, total_steps=STEPS,
            dp_collective=mode,
            sketch=SketchSettings(enabled=True, k_max=9, beta=0.9,
                                  recon_mode="fast"))
        fused, ref = train(cfg, mk("fused")), train(cfg, mk("per_node"))
        gap = abs(fused - ref)
        print(f"lag gap {gap:.4f} (per_node {ref:.4f} fused {fused:.4f})")
        assert gap <= 0.05, (ref, fused)
        print("OK")
    """, devices=4)
    assert "OK" in out


# ---------------------------------------------------------------------------
# ISSUE 5 differential tier: the overlap two-phase DP schedule vs the
# per-node reference — sketched-backprop consumption with NO lag
# ---------------------------------------------------------------------------


OVERLAP_LM_CODE = """
    import dataclasses, re
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs import get_arch, reduced
    from repro.data.synthetic import lm_batch
    from repro.models.transformer import SketchSettings
    from repro.optim.compression import CompressionConfig
    from repro.sketches import tree_wire_spec
    from repro.train.state import RunConfig, init_train_state
    from repro.train.step import make_dp_train_step

    STEPS = {steps}
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    cfg = reduced(get_arch("tinyllama-1.1b"))          # sketch_mode=backprop
    ccfg = CompressionConfig(mode="countsketch", cs_rows=5,
                             cs_cols=512, cs_k=256, cs_momentum=0.0)
    key = jax.random.PRNGKey(0)
    tokens, labels = lm_batch(jax.random.PRNGKey(2), 8, 16,
                              cfg.vocab_size)
    batch = {{"tokens": tokens, "labels": labels}}

    def mk(mode, comp):
        return RunConfig(seq_len=16, global_batch=8,
                         dp_axis_name="data", dp_workers=4,
                         compression=comp, dp_collective=mode,
                         sketch=SketchSettings(enabled=True, k_max=9,
                                               beta=0.9,
                                               recon_mode="fast"))

    for comp in {wires}:
        states = {{}}
        for mode in ("per_node", "overlap"):
            run = mk(mode, comp)
            state = init_train_state(key, cfg, run)
            state = jax.device_put(state, NamedSharding(mesh, P()))
            step = jax.jit(make_dp_train_step(cfg, run, mesh))
            for _ in range(STEPS):
                state, m = step(state, batch)
            states[mode] = (state, m)
        a, b = states["per_node"], states["overlap"]
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            # NO lag allowance: sketched-backprop consumption under
            # overlap is the current step's merged triple — full state
            # AND metrics must be BITWISE equal to per_node
            assert np.array_equal(np.asarray(x), np.asarray(y)), \\
                "overlap step diverged from per_node"
        print("bitwise OK", "countsketch" if comp else "dense")

    # HLO: <= 2 all-reduces, the sketch psum scheduled BEFORE the
    # backward — its merged result is consumed (the triple fold the
    # backward reads) strictly before the gradient-wire all-reduce,
    # whose operand the backward produces.
    run = mk("overlap", None)
    state = init_train_state(key, cfg, run)
    early_total = tree_wire_spec(state.sketch).total
    txt = jax.jit(make_dp_train_step(cfg, run, mesh)).lower(
        jax.device_put(state, NamedSharding(mesh, P())),
        batch).compile().as_text()
    colls = re.findall(
        r"= \\S+ (all-reduce|all-gather|reduce-scatter|"
        r"all-to-all|collective-permute)", txt)
    assert len(colls) == 2 and set(colls) == {{"all-reduce"}}, colls
    entry = txt[txt.index("ENTRY"):]
    lines = entry.splitlines()
    ars = [(i, ln) for i, ln in enumerate(lines)
           if re.search(r"= f32\\[\\d+\\]\\S* all-reduce\\(", ln)]
    assert len(ars) == 2, [ln[:80] for _, ln in ars]
    sizes = [int(re.search(r"f32\\[(\\d+)\\]", ln).group(1))
             for _, ln in ars]
    assert sizes[0] == early_total, (sizes, early_total)
    assert sizes[1] > sizes[0], sizes
    early_name = re.match(r"\\s*(\\S+)", ars[0][1]).group(1)
    consumers = [i for i, ln in enumerate(lines)
                 if early_name + ")" in ln or early_name + "," in ln
                 or early_name + " " in ln]
    consumers = [i for i in consumers if i != ars[0][0]]
    assert consumers and min(consumers) < ars[1][0], \\
        (min(consumers, default=-1), ars[1][0])
    print("overlap HLO schedule OK", sizes)
    print("OK")
"""


OVERLAP_MLP_CODE = """
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.configs.paper import MLPConfig
    from repro.core.sketch import SketchConfig
    from repro.optim.adamw import AdamWConfig, init_adamw
    from repro.models.mlp import mlp_init
    from repro.train.paper_trainer import init_mlp_sketch, make_dp_step

    STEPS = {steps}
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    W, Tl = 4, 8
    cfg = MLPConfig(name="t", d_in=20, d_hidden=28, d_out=4,
                    num_hidden_layers=3, activation="tanh",
                    batch_size=Tl, learning_rate=1e-3)
    scfg = SketchConfig(rank=3, max_rank=4, beta=0.9, batch_size=Tl)
    opt_cfg = AdamWConfig(lr=1e-3, b2=0.999)
    key = jax.random.PRNGKey(0)
    kp, ks, kx = jax.random.split(key, 3)
    params0 = mlp_init(kp, cfg)
    x = jax.random.normal(kx, (W * Tl, cfg.d_in))
    y = jax.random.randint(jax.random.fold_in(kx, 1), (W * Tl,), 0,
                           cfg.d_out)

    for variant in {variants}:
        step_pn = make_dp_step(cfg, scfg, variant, opt_cfg, mesh,
                               collective="per_node")
        step_ov = make_dp_step(cfg, scfg, variant, opt_cfg, mesh,
                               collective="overlap")
        p = params0
        opt = init_adamw(params0, opt_cfg)
        sk = init_mlp_sketch(ks, cfg, scfg, variant)
        # Both layouts step from the SAME reference state each
        # iteration (the per_node trajectory), so the per-step bitwise
        # contract stays observable along a real multi-step run: the
        # gradient-derived leaves carry last-ulp cross-program fusion
        # noise (XLA:CPU re-fuses the freely-inlined MLP backward per
        # program — the LM e2e, whose backward is scan/remat-bounded,
        # is the fully-bitwise witness), and letting the two
        # trajectories free-run would feed that noise back into the
        # step-2 observations.
        for s in range(STEPS):
            pa, oa, ska, la = step_pn(p, opt, sk, x, y)
            pb, ob, skb, lb = step_ov(p, opt, sk, x, y)
            # sketch trees + loss: BITWISE (current-step DP-exact merge)
            for u, v in zip(jax.tree.leaves((ska, la)),
                            jax.tree.leaves((skb, lb))):
                assert np.array_equal(np.asarray(u), np.asarray(v)), \\
                    (variant, s, "tree/loss diverged")
            for u, v in zip(jax.tree.leaves((pa, oa)),
                            jax.tree.leaves((pb, ob))):
                np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                           atol=1e-6, rtol=1e-6)
            p, opt, sk = pa, oa, ska
        print(variant, "trees+loss bitwise OK, grads ulp-close OK")
    print("OK")
"""


@pytest.mark.slow
def test_overlap_partition_psum_bitwise_parity_mlp_variant_trees():
    """Subsystem-level overlap differential at W=4, one tree per MLP
    variant: routing the increments through the overlap schedule's
    machinery — `partition_segments` early/late split + the
    barrier-pinned early flat psum + the apply helpers — must be
    BITWISE identical to the per-node `ema_triple_update(axis_name=...)`
    psums (paper kind), and for the ragged corange kind the new
    increment/apply decomposition must be bitwise the canonical
    `corange_triple_update` both per worker and under per-leaf psums."""
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.configs.paper import MLPConfig
        from repro.core.sketch import SketchConfig
        from repro.sketches import (
            corange_apply_increment, corange_triple_increment,
            corange_triple_update, ema_triple_update, partition_segments)
        from repro.sketches.update import ema_apply_increment, \\
            ema_triple_increment
        from repro.parallel.collectives import psum_flat_segments
        from repro.train.paper_trainer import init_mlp_sketch

        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        W, Tl = 4, 8

        def paper_tree(variant, rank, beta, seed):
            cfg = MLPConfig(name="t", d_in=20, d_hidden=28, d_out=4,
                            num_hidden_layers=3, activation="tanh",
                            batch_size=Tl, learning_rate=1e-3)
            scfg = SketchConfig(rank=rank, max_rank=4, beta=beta,
                                batch_size=Tl)
            sk = init_mlp_sketch(jax.random.PRNGKey(seed), cfg, scfg,
                                 variant)
            if variant != "corange":
                sk = dataclasses.replace(sk, nodes={
                    "hidden": dataclasses.replace(
                        sk.nodes["hidden"],
                        x=0.1 * sk.nodes["hidden"].psi[..., None, :] *
                        jnp.ones((28, 1)))})
            return cfg, scfg, sk

        variants = [("sketched_fixed", 3, 0.9, 0),
                    ("sketched_adaptive", 2, 0.9, 1),
                    ("monitor", 4, 0.95, 2),
                    ("corange", 3, 0.9, 3)]
        for variant, rank, beta, seed in variants:
            cfg, scfg, sk = paper_tree(variant, rank, beta, seed)
            node = sk.nodes["hidden"]
            L = cfg.num_hidden_layers
            ka = sk.k_active
            d = cfg.d_hidden
            acts = jax.random.normal(jax.random.PRNGKey(100 + seed),
                                     (L, W * Tl, d))

            if variant == "corange":
                key = jax.random.PRNGKey(7)
                nz = lambda s, i: 0.05 * jax.random.normal(
                    jax.random.fold_in(key, i), s)
                xc = nz(node.x.shape, 0)
                yc = nz(node.y.shape, 1)
                zc = nz(node.z.shape, 2)

                # (a) increment + apply == THE canonical update, per
                # worker (no DP), nonzero state, bitwise
                a0 = acts[:, :Tl]
                for l in range(L):
                    want = corange_triple_update(
                        xc[l], yc[l], zc[l], a0[l], sk.proj,
                        scfg.beta, ka)
                    ix, iy, iz = corange_triple_increment(
                        xc[l], yc[l], zc[l], a0[l], sk.proj,
                        scfg.beta, ka)
                    got = corange_apply_increment(
                        xc[l], yc[l], zc[l], ix, iy, iz, scfg.beta, ka)
                    for g, w in zip(got, want):
                        assert np.array_equal(np.asarray(g),
                                              np.asarray(w))
                print("corange increment/apply == update OK")

                # (b) partitioned early psum of the ragged increments ==
                # per-leaf psums, then bitwise through the apply
                def incs(a_sh):
                    outs = [corange_triple_increment(
                        xc[l], yc[l], zc[l], a_sh[l], sk.proj,
                        scfg.beta, ka) for l in range(L)]
                    return {"hidden": {
                        "x": jnp.stack([o[0] for o in outs]),
                        "y": jnp.stack([o[1] for o in outs]),
                        "z": jnp.stack([o[2] for o in outs])}}

                def apply_(m):
                    outs = [corange_apply_increment(
                        xc[l], yc[l], zc[l], m["hidden"]["x"][l],
                        m["hidden"]["y"][l], m["hidden"]["z"][l],
                        scfg.beta, ka) for l in range(L)]
                    return {"x": jnp.stack([o[0] for o in outs]),
                            "y": jnp.stack([o[1] for o in outs]),
                            "z": jnp.stack([o[2] for o in outs])}

                def overlap(a_sh):
                    early, late = partition_segments(
                        {"sketch": incs(a_sh),
                         "n": jnp.ones((), jnp.float32)})
                    assert set(early) == {"sketch"} and \\
                        set(late) == {"n"}
                    merged = psum_flat_segments(
                        early["sketch"], "data",
                        name="overlap_sketch", barrier=True)
                    return apply_(merged)

                def per_leaf(a_sh):
                    pm = lambda t: jax.lax.psum(t, "data")
                    return apply_(jax.tree.map(pm, incs(a_sh)))

                sh = lambda f: jax.jit(shard_map(
                    lambda a: f(a.reshape(L, Tl, d)),
                    mesh=mesh, in_specs=P(None, "data"), out_specs=P(),
                    check_vma=False))
                got = sh(overlap)(acts)
                want = sh(per_leaf)(acts)
                for g, w in zip(jax.tree.leaves(got),
                                jax.tree.leaves(want)):
                    assert np.array_equal(np.asarray(g), np.asarray(w))
                print("corange overlap partition bitwise OK")
                continue

            # paper-kind trees: the overlap early psum + apply vs the
            # per-node reference psums
            def per_node(a_sh):
                def one(l):
                    return ema_triple_update(
                        node.x[l], node.y[l], node.z[l], a_sh[l],
                        sk.proj["upsilon"], sk.proj["omega"],
                        sk.proj["phi"], node.psi[l], scfg.beta, ka,
                        axis_name="data")
                outs = [one(l) for l in range(L)]
                return {"hidden": {
                    "x": jnp.stack([o[0] for o in outs]),
                    "y": jnp.stack([o[1] for o in outs]),
                    "z": jnp.stack([o[2] for o in outs])}}

            def overlap(a_sh):
                def one(l):
                    return ema_triple_increment(
                        node.x[l], node.y[l], node.z[l], a_sh[l],
                        sk.proj["upsilon"], sk.proj["omega"],
                        sk.proj["phi"], node.psi[l], scfg.beta, ka)
                outs = [one(l) for l in range(L)]
                leaves = {"hidden": {
                    "x": jnp.stack([o[0] for o in outs]),
                    "y": jnp.stack([o[1] for o in outs]),
                    "z": jnp.stack([o[2] for o in outs])}}
                early, late = partition_segments({
                    "sketch": leaves,
                    "n": jnp.ones((), jnp.float32),
                    "scalars": jnp.zeros((3,), jnp.float32)})
                assert set(early) == {"sketch"}
                assert set(late) == {"n", "scalars"}
                merged = psum_flat_segments(
                    early["sketch"], "data", name="overlap_sketch",
                    barrier=True)
                m = merged["hidden"]
                return {"hidden": {
                    "x": ema_apply_increment(node.x, m["x"], scfg.beta,
                                             ka),
                    "y": ema_apply_increment(node.y, m["y"], scfg.beta,
                                             ka),
                    "z": ema_apply_increment(node.z, m["z"], scfg.beta,
                                             ka)}}

            sh = lambda f: jax.jit(shard_map(
                lambda a: f(a.reshape(L, Tl, d)),
                mesh=mesh, in_specs=P(None, "data"), out_specs=P(),
                check_vma=False))
            got = sh(overlap)(acts)
            want = sh(per_node)(acts)
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                assert np.array_equal(np.asarray(g), np.asarray(w)), \\
                    variant
            print(variant, "overlap partition apply bitwise OK")
        print("OK")
    """, devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_overlap_step_bitwise_vs_per_node_sketched_backprop_w4():
    """ISSUE 5 acceptance, LM half: with dp_collective="overlap" at W=4
    the sketched-backprop LM is BITWISE equal to per_node over 3 full
    steps — state AND metrics, dense and countsketch wires; the lag
    allowance of the fused layout does not apply. The compiled step
    holds <= 2 all-reduces, with the sketch psum scheduled before the
    backward (its merged triple is consumed before the gradient-wire
    all-reduce the backward feeds)."""
    out = _run(OVERLAP_LM_CODE.format(
        steps=3, wires="(None, ccfg)"), devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_overlap_mlp_e2e_vs_per_node_w4():
    """ISSUE 5 acceptance, MLP half (full variant set, 3 steps): the
    e2e DP MLP step under the overlap schedule reproduces the per-node
    reference — sketch trees and loss bitwise, gradient-derived state
    to last-ulp compiler noise."""
    out = _run(OVERLAP_MLP_CODE.format(
        steps=3,
        variants="('sketched_fixed', 'sketched_adaptive', 'monitor')"),
        devices=4)
    assert "OK" in out


@pytest.mark.dp_differential
def test_dp_differential_mlp_sketched_backprop_w4():
    """Per-PR reduced differential (CI job `differential-w4`): ONE
    sketched-backprop MLP variant, 2 steps at W=4 — overlap vs
    per_node, trees + loss bitwise."""
    out = _run(OVERLAP_MLP_CODE.format(
        steps=2, variants="('sketched_fixed',)"), devices=4)
    assert "OK" in out


@pytest.mark.dp_differential
def test_dp_differential_monitor_lm_w4():
    """Per-PR reduced differential (CI job `differential-w4`): the
    monitor LM, 2 steps at W=4 — under overlap a monitor-only tree has
    no backward consumer, so the step must stay on the fused
    single-collective fast path AND remain bitwise equal to
    per_node."""
    out = _run("""
        import dataclasses, re
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_arch, reduced
        from repro.data.synthetic import lm_batch
        from repro.models.transformer import SketchSettings
        from repro.train.state import RunConfig, init_train_state
        from repro.train.step import make_dp_train_step

        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        cfg = dataclasses.replace(reduced(get_arch("tinyllama-1.1b")),
                                  sketch_mode="monitor")
        key = jax.random.PRNGKey(0)
        tokens, labels = lm_batch(jax.random.PRNGKey(2), 8, 16,
                                  cfg.vocab_size)
        batch = {"tokens": tokens, "labels": labels}
        mk = lambda mode: RunConfig(
            seq_len=16, global_batch=8, dp_axis_name="data",
            dp_workers=4, dp_collective=mode,
            sketch=SketchSettings(enabled=True, k_max=9, beta=0.9,
                                  recon_mode="fast"))
        states = {}
        for mode in ("per_node", "overlap"):
            run = mk(mode)
            state = init_train_state(key, cfg, run)
            state = jax.device_put(state, NamedSharding(mesh, P()))
            step = jax.jit(make_dp_train_step(cfg, run, mesh))
            for _ in range(2):
                state, m = step(state, batch)
            states[mode] = (state, m)
        for x, y in zip(jax.tree.leaves(states["per_node"]),
                        jax.tree.leaves(states["overlap"])):
            assert np.array_equal(np.asarray(x), np.asarray(y)), \\
                "overlap monitor fast path diverged from per_node"

        run = mk("overlap")
        state = init_train_state(key, cfg, run)
        txt = jax.jit(make_dp_train_step(cfg, run, mesh)).lower(
            jax.device_put(state, NamedSharding(mesh, P())),
            batch).compile().as_text()
        colls = re.findall(
            r"= \\S+ (all-reduce|all-gather|reduce-scatter|"
            r"all-to-all|collective-permute)", txt)
        assert len(colls) == 1 and colls[0] == "all-reduce", colls
        print("OK")
    """, devices=4)
    assert "OK" in out


@pytest.mark.dp_differential
def test_dp_differential_psparse_w4():
    """Per-PR reduced differential (CI job `differential-w4`), psparse
    half (DESIGN.md §13): at W=4 both DP merge layouts of the
    p-sparsified increments — the fused flat psum and the overlap
    early-psum schedule — must be BITWISE identical to the per-node
    `proj_triple_update(axis_name=...)` reference; the per-worker
    kernel-route increment must be bitwise what the jnp oracle
    (`psparse_update_ref` on a zero sketch) computes, and the
    production gather fast path allclose to it."""
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.configs.paper import MLPConfig
        from repro.core.sketch import SketchConfig
        from repro.kernels.ref import psparse_update_ref
        from repro.parallel.collectives import psum_flat_segments
        from repro.sketches import partition_segments, \\
            proj_triple_increment, proj_triple_update
        from repro.sketches.update import ema_apply_increment, \\
            mask_columns
        from repro.train.paper_trainer import init_mlp_sketch

        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        W, Tl = 4, 8
        cfg = MLPConfig(name="t", d_in=20, d_hidden=28, d_out=4,
                        num_hidden_layers=3, activation="tanh",
                        batch_size=Tl, learning_rate=1e-3)
        scfg = SketchConfig(rank=3, max_rank=4, beta=0.9, batch_size=Tl,
                            proj_kind="psparse", proj_density=0.1)
        sk = init_mlp_sketch(jax.random.PRNGKey(0), cfg, scfg,
                             "sketched_fixed")
        # nonzero state so beta*x + inc is exercised, not just the inc
        sk = dataclasses.replace(sk, nodes={
            "hidden": dataclasses.replace(
                sk.nodes["hidden"],
                x=0.1 * sk.nodes["hidden"].psi[..., None, :] *
                jnp.ones((28, 1)))})
        node = sk.nodes["hidden"]
        L, d, ka = cfg.num_hidden_layers, cfg.d_hidden, sk.k_active
        acts = jax.random.normal(jax.random.PRNGKey(100), (L, W * Tl, d))

        # (a) single-worker increments vs the kernel's jnp oracle on a
        # zero sketch: kernel route bitwise, gather fast path allclose
        a0 = acts[:, :Tl]
        for l in range(L):
            z = jnp.zeros_like(node.x[l])
            ps = mask_columns(node.psi[l], ka)
            ox, oy, oz = psparse_update_ref(
                a0[l], z, z, z, sk.proj.params, ps, beta=scfg.beta,
                m=sk.proj.m)
            # the oracle leaves x/y columns >= k_active live; the
            # increment path masks them (z is masked through psi)
            ox, oy = mask_columns(ox, ka), mask_columns(oy, ka)
            kx, ky, kz = proj_triple_increment(
                node.x[l], node.y[l], node.z[l], a0[l], sk.proj,
                node.psi[l], scfg.beta, ka, use_kernel=True)
            for g, w in zip((kx, ky, kz), (ox, oy, oz)):
                assert np.array_equal(np.asarray(g), np.asarray(w)), l
            fx, fy, fz = proj_triple_increment(
                node.x[l], node.y[l], node.z[l], a0[l], sk.proj,
                node.psi[l], scfg.beta, ka)
            for g, w in zip((fx, fy, fz), (ox, oy, oz)):
                np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                           atol=1e-5)
        print("psparse kernel-route == jnp oracle bitwise OK")

        def incs(a_sh):
            outs = [proj_triple_increment(
                node.x[l], node.y[l], node.z[l], a_sh[l], sk.proj,
                node.psi[l], scfg.beta, ka) for l in range(L)]
            return {"hidden": {
                "x": jnp.stack([o[0] for o in outs]),
                "y": jnp.stack([o[1] for o in outs]),
                "z": jnp.stack([o[2] for o in outs])}}

        def apply_(m):
            m = m["hidden"]
            return {"hidden": {
                "x": ema_apply_increment(node.x, m["x"], scfg.beta, ka),
                "y": ema_apply_increment(node.y, m["y"], scfg.beta, ka),
                "z": ema_apply_increment(node.z, m["z"], scfg.beta,
                                         ka)}}

        def per_node(a_sh):
            outs = [proj_triple_update(
                node.x[l], node.y[l], node.z[l], a_sh[l], sk.proj,
                node.psi[l], scfg.beta, ka, axis_name="data")
                for l in range(L)]
            return {"hidden": {
                "x": jnp.stack([o[0] for o in outs]),
                "y": jnp.stack([o[1] for o in outs]),
                "z": jnp.stack([o[2] for o in outs])}}

        def fused(a_sh):
            return apply_(psum_flat_segments(incs(a_sh), "data"))

        def overlap(a_sh):
            early, late = partition_segments(
                {"sketch": incs(a_sh),
                 "n": jnp.ones((), jnp.float32)})
            assert set(early) == {"sketch"} and set(late) == {"n"}
            return apply_(psum_flat_segments(
                early["sketch"], "data", name="overlap_sketch",
                barrier=True))

        sh = lambda f: jax.jit(shard_map(
            lambda a: f(a.reshape(L, Tl, d)),
            mesh=mesh, in_specs=P(None, "data"), out_specs=P(),
            check_vma=False))
        want = sh(per_node)(acts)
        for name, f in (("fused", fused), ("overlap", overlap)):
            got = sh(f)(acts)
            for g, w in zip(jax.tree.leaves(got),
                            jax.tree.leaves(want)):
                assert np.array_equal(np.asarray(g), np.asarray(w)), \\
                    name
            print("psparse", name, "bitwise vs per_node OK")
        print("OK")
    """, devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_int8_error_feedback_survives_checkpoint_per_worker_w4():
    """Checkpoint round-trip of the per-worker error-feedback residuals
    under wire_dtype=int8 (they carry quantization error too): the
    per_worker_v1 layout stacks every worker's buffer on a leading
    (W, ...) axis — NO pmean merge destroys the decomposition at save
    time (the PR 2 elastic-restart gap, closed by DESIGN.md §12) — and
    a Checkpointer save/restore + scatter hands each worker its exact
    row back, bitwise."""
    out = _run("""
        import dataclasses, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.checkpoint.checkpointer import (
            RESIDUAL_LAYOUT, Checkpointer, gather_per_worker,
            scatter_per_worker)
        from repro.configs import get_arch, reduced
        from repro.data.synthetic import lm_batch
        from repro.models.transformer import SketchSettings
        from repro.optim.compression import CompressionConfig
        from repro.train.state import RunConfig, init_train_state
        from repro.train.step import make_dp_train_step

        W = 4
        mesh = Mesh(np.array(jax.devices()[:W]), ("data",))
        cfg = dataclasses.replace(reduced(get_arch("tinyllama-1.1b")),
                                  sketch_mode="monitor")
        run = RunConfig(
            seq_len=16, global_batch=8, dp_axis_name="data",
            dp_workers=W, warmup_steps=2, total_steps=10,
            compression=CompressionConfig(
                mode="countsketch", cs_rows=5, cs_cols=512, cs_k=256,
                cs_momentum=0.0, wire_dtype="int8"),
            sketch=SketchSettings(enabled=True, k_max=9, beta=0.9,
                                  recon_mode="fast"))
        key = jax.random.PRNGKey(0)
        state = init_train_state(key, cfg, run)
        state = jax.device_put(state, NamedSharding(mesh, P()))
        step = jax.jit(make_dp_train_step(cfg, run, mesh))
        for s in range(3):
            tok, lab = lm_batch(jax.random.fold_in(key, s), 8, 16,
                                cfg.vocab_size)
            state, _ = step(state, {"tokens": tok, "labels": lab})

        err = state.opt["err"]
        stacked = gather_per_worker(err, mesh, "data")
        rows = [np.asarray(l) for l in jax.tree.leaves(stacked)]
        assert all(r.shape[0] == W for r in rows)
        # the residuals genuinely diverged per worker — the stacking is
        # load-bearing, not a W-fold copy
        assert any(len({r[w].tobytes() for w in range(W)}) > 1
                   for r in rows), "residuals identical across workers"

        # save stacked, restore, scatter: every worker gets its exact
        # buffer back (regather and compare bitwise)
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d, keep=1)
            ck.save(3, stacked,
                    metadata={"residual_layout": RESIDUAL_LAYOUT,
                              "dp_workers": W})
            meta = ck.metadata()
            assert meta["residual_layout"] == RESIDUAL_LAYOUT
            assert meta["dp_workers"] == W
            restored, _ = ck.restore(
                jax.tree.map(np.asarray, stacked))
        back = scatter_per_worker(
            jax.tree.map(jnp.asarray, restored), mesh, "data")
        again = gather_per_worker(back, mesh, "data")
        for a, b in zip(jax.tree.leaves(stacked),
                        jax.tree.leaves(again)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \\
                "per-worker residual round-trip not bitwise"
        print("OK")
    """, devices=4)
    assert "OK" in out


# ---------------------------------------------------------------------------
# ISSUE 7 differential tier: mesh-sharded sketch state — W=4 dp workers
# on a (pod=2, data=2, model=2) mesh with the ZeRO-style reduce-scatter
# merge vs the replicated per-node reference on a 1D ("data",) mesh
# ---------------------------------------------------------------------------


RS_LM_CODE = """
    import re
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_arch, reduced
    from repro.data.synthetic import lm_batch
    from repro.models.transformer import SketchSettings
    from repro.sketches import unshard_tree
    from repro.train.state import RunConfig, init_train_state
    from repro.train.step import collective_plan, make_dp_train_step

    STEPS = __STEPS__
    cfg = reduced(get_arch("tinyllama-1.1b"))      # sketch_mode=backprop
    mesh8 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                          axis_types=(jax.sharding.AxisType.Auto,) * 3)
    mesh4 = Mesh(np.array(jax.devices()[:4]), ("data",))

    def mk(dp_axis, collective, merge):
        return RunConfig(seq_len=16, global_batch=8,
                         sketch=SketchSettings(enabled=True, k_max=9),
                         dp_axis_name=dp_axis, dp_workers=4,
                         dp_collective=collective, dp_merge=merge,
                         total_steps=STEPS + 1, warmup_steps=1)

    run_ref = mk("data", "per_node", "psum")
    run_rs = mk(("pod", "data"), "overlap", "reduce_scatter")
    st_ref = init_train_state(jax.random.PRNGKey(0), cfg, run_ref)
    st_rs = init_train_state(jax.random.PRNGKey(0), cfg, run_rs)
    step_ref = jax.jit(make_dp_train_step(cfg, run_ref, mesh4))
    step_rs = jax.jit(make_dp_train_step(cfg, run_rs, mesh8))
    for s in range(STEPS):
        tok, lab = lm_batch(jax.random.fold_in(jax.random.PRNGKey(2), s),
                            8, 16, cfg.vocab_size)
        b = {"tokens": tok, "labels": lab}
        st_ref, m_ref = step_ref(st_ref, b)
        st_rs, m_rs = step_rs(st_rs, b)
        for k in ("loss", "grad_norm"):
            assert np.array_equal(np.asarray(m_ref[k]),
                                  np.asarray(m_rs[k])), (s, k)

    # replicated halves of the state: bitwise across the two meshes
    for lref, lrs in zip(jax.tree.leaves((st_ref.params, st_ref.opt,
                                          st_ref.monitor)),
                         jax.tree.leaves((st_rs.params, st_rs.opt,
                                          st_rs.monitor))):
        assert np.array_equal(np.asarray(lref), np.asarray(lrs)), \\
            "rs step diverged from the replicated reference"

    # worker shards reassemble to the reference's replicated NodeTree;
    # dp worker of device (p, d, m) is p*2 + d, the model-axis pair of
    # every dp worker holds an IDENTICAL shard
    by_dev = {s.device.id: np.asarray(s.data)
              for s in st_rs.sketch.flat.addressable_shards}
    ids = np.vectorize(lambda dv: dv.id)(mesh8.devices)  # (pod,data,model)
    for p in range(2):
        for d in range(2):
            assert np.array_equal(by_dev[ids[p, d, 0]],
                                  by_dev[ids[p, d, 1]]), (p, d)
    full = np.concatenate([by_dev[ids[p, d, 0]]
                           for p in range(2) for d in range(2)])
    rebuilt = unshard_tree(st_rs.sketch, jnp.asarray(full))
    for name in st_ref.sketch.nodes:
        for leaf in ("x", "y", "z", "psi"):
            assert np.array_equal(
                np.asarray(getattr(st_ref.sketch.nodes[name], leaf)),
                np.asarray(getattr(rebuilt.nodes[name], leaf))), \\
                (name, leaf)
    print("rs bitwise OK")
"""


RS_HLO_CHECK = """
    # per-axis HLO collective counts: exactly ONE reduce-scatter + ONE
    # all-gather + ONE all-reduce, every one on the flattened
    # (pod, data) supergroup — replica groups {0,2,4,6},{1,3,5,7} are
    # the dp workers at fixed model coordinate — and ZERO model-axis
    # collectives (TP traffic is GSPMD-implicit, none is step-issued
    # on this replicated-weights debug config)
    txt = jax.jit(make_dp_train_step(cfg, run_rs, mesh8)).lower(
        init_train_state(jax.random.PRNGKey(0), cfg, run_rs),
        b).compile().as_text()
    found = re.findall(
        r"= \\S+ (all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute)\\(.*?replica_groups=(\\{(?:\\{[0-9,]*\\},?)*\\})",
        txt)
    kinds = sorted(k for k, _ in found)
    assert kinds == ["all-gather", "all-reduce", "reduce-scatter"], kinds
    dp_groups = "{{0,2,4,6},{1,3,5,7}}"
    for k, g in found:
        assert g == dp_groups, (k, g)

    # the structural plan agrees with the compiled HLO
    plan = collective_plan(cfg, run_rs, mesh_shape=dict(mesh8.shape))
    assert plan["layout"] == "rs_overlap"
    assert plan["by_kind"] == {"all_reduce": 1, "reduce_scatter": 1,
                               "all_gather": 1}
    assert plan["per_axis"] == {"pod+data": 3, "model": 0}
    print("rs HLO per-axis OK")
"""


RS_TAIL = """
    print("OK")
"""


@pytest.mark.slow
def test_rs_merge_step_bitwise_vs_replicated_w8():
    """ISSUE 7 acceptance, e2e half: the sketched-backprop LM under
    dp_merge="reduce_scatter" on the (2,2,2) pod x data x model mesh —
    each dp worker owning 1/4 of the merged triple buffer — is BITWISE
    equal to the replicated per-node reference on a 1D mesh over 3 full
    steps (loss, grad_norm, params, optimizer, monitor ring), the
    worker shards reassemble to the reference NodeTree exactly, and the
    compiled HLO carries exactly RS + AG + AR on the dp supergroup with
    zero model-axis collectives."""
    out = _run(RS_LM_CODE.replace("__STEPS__", "3")
               + RS_HLO_CHECK + RS_TAIL, devices=8)
    assert "OK" in out


@pytest.mark.dp_differential
def test_dp_differential_rs_merge_w8():
    """Per-PR reduced differential (CI job `differential-w4`): 2 steps
    of the reduce-scatter merge on the (2,2,2) mesh vs the replicated
    1D reference — state bitwise, shards reassemble exactly."""
    out = _run(RS_LM_CODE.replace("__STEPS__", "2") + RS_TAIL,
               devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_tuple_axis_ema_psum_matches_1d_w8():
    """Subsystem guarantee under the rs tentpole: `ema_triple_update`
    with a TUPLE axis_name — psum over the flattened ("pod","data")
    supergroup of the (2,2,2) mesh — is BITWISE the 1D ("data",) psum
    at the same worker count (CPU psum reduces in dp-rank order on
    both)."""
    out = _run("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.sketches import ema_triple_update

        mesh8 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                          axis_types=(jax.sharding.AxisType.Auto,) * 3)
        mesh4 = Mesh(np.array(jax.devices()[:4]), ("data",))
        W, Tl, d, k = 4, 16, 24, 9
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        a = jax.random.normal(ks[0], (W * Tl, d))
        ups, omg, phi = (jax.random.normal(ks[i], (Tl, k))
                         for i in (1, 2, 3))
        psi = jax.random.normal(ks[4], (k,))
        x0 = 0.1 * jax.random.normal(ks[5], (d, k))
        upd = functools.partial(
            ema_triple_update, upsilon=ups, omega=omg, phi=phi, psi=psi,
            beta=0.9, k_active=jnp.asarray(7))

        ref = jax.jit(shard_map(
            lambda sh: upd(x0, x0, x0, a=sh, axis_name="data"),
            mesh=mesh4, in_specs=P("data"), out_specs=P(),
            check_vma=False))(a)
        got = jax.jit(shard_map(
            lambda sh: upd(x0, x0, x0, a=sh,
                           axis_name=("pod", "data")),
            mesh=mesh8, in_specs=P(("pod", "data")), out_specs=P(),
            check_vma=False))(a)
        for g, r in zip(got, ref):
            assert np.array_equal(np.asarray(g), np.asarray(r)), \\
                "tuple-axis psum is not bitwise the 1D psum"
        print("OK")
    """, devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_rs_loop_checkpoint_resume_preserves_worker_shards_w8():
    """ISSUE 7 acceptance, persistence half: run_training under the rs
    merge + countsketch wire saves per-worker sketch shards AND
    error-feedback residuals natively (per_worker_v1 + sharded-v1
    metadata tags); a mid-run kill + fresh run_training call resumes
    from the step-2 checkpoint and lands BITWISE on the uninterrupted
    4-step trajectory — including every worker's distinct buffers."""
    out = _run("""
        import os, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint.checkpointer import (
            RESIDUAL_LAYOUT, Checkpointer, gather_per_worker)
        from repro.configs import get_arch, reduced
        from repro.models.transformer import SketchSettings
        from repro.optim.compression import CompressionConfig
        from repro.train.loop import LoopConfig, run_training
        from repro.train.state import RunConfig

        cfg = reduced(get_arch("tinyllama-1.1b"))
        mesh8 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                          axis_types=(jax.sharding.AxisType.Auto,) * 3)

        def mk_run():
            return RunConfig(
                seq_len=16, global_batch=8,
                sketch=SketchSettings(enabled=True, k_max=9),
                dp_axis_name=("pod", "data"), dp_workers=4,
                dp_collective="overlap", dp_merge="reduce_scatter",
                compression=CompressionConfig(
                    mode="countsketch", cs_rows=5, cs_cols=512,
                    cs_k=256, cs_momentum=0.0),
                total_steps=4, warmup_steps=1)

        def per_worker(state):
            pw = {"flat": state.sketch.flat, "err": state.opt["err"]}
            return jax.tree.map(
                np.asarray,
                gather_per_worker(pw, mesh8, ("pod", "data")))

        def mk_loop(d, n):
            return LoopConfig(num_steps=n, ckpt_every=2, log_every=10,
                              ckpt_dir=d)

        with tempfile.TemporaryDirectory() as d:
            straight, resumed = (os.path.join(d, n) for n in "ab")
            sa, ha = run_training(cfg, mk_run(), mk_loop(straight, 4),
                                  dp_mesh=mesh8)
            # interrupted twin: stop after 2 steps...
            run_training(cfg, mk_run(), mk_loop(resumed, 2),
                         dp_mesh=mesh8)
            meta = Checkpointer(resumed).metadata()
            assert meta["residual_layout"] == RESIDUAL_LAYOUT
            assert meta["dp_workers"] == 4
            assert meta["sketch_layout"] == "sharded-v1"
            # ...then a FRESH call restores at step 2 and finishes
            sb, hb = run_training(cfg, mk_run(), mk_loop(resumed, 4),
                                  dp_mesh=mesh8)

        assert [h["loss"] for h in ha[2:]] == [h["loss"] for h in hb]
        for a, b in zip(jax.tree.leaves((sa.params, sa.monitor)),
                        jax.tree.leaves((sb.params, sb.monitor))):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \\
                "resume diverged from the uninterrupted run"
        pa, pb = per_worker(sa), per_worker(sb)
        for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            assert np.array_equal(a, b), \\
                "per-worker buffers not preserved across restart"
        # the stacked err rows genuinely differ across workers — the
        # per-worker layout is load-bearing
        assert any(len({np.asarray(l)[w].tobytes() for w in range(4)}) > 1
                   for l in jax.tree.leaves(pb["err"])), \\
            "residuals identical across workers"
        print("OK")
    """, devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_sketch_tp_specs_and_dryrun_report_w8():
    """Sketch-state sharding resolution on the (2,2,2) debug mesh: a
    node's (..., d, k) triple shards d over its consumer's TP axis plus
    the ZeRO dp axes, psi stays replicated, the shared (T, k)
    projections shard rows over dp; and the dry-run report certifies
    gemma3-27b / mixtral-8x22b end up with every >=1 MiB triple leaf
    sharded (an OOM-sized replicated sketch fails the dry run)."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs import SHAPES, get_arch
        from repro.launch.dryrun import (
            make_run_config, sketch_sharding_report)
        from repro.launch.mesh import make_mesh, rules_for_mesh
        from repro.parallel.sharding import (
            param_shardings, spec_for_sketch, use_rules)
        from repro.train.state import abstract_train_state

        mesh = make_mesh(2, 2, n_pod=2)
        rules = rules_for_mesh(mesh)
        f32 = jnp.float32
        trip = jax.ShapeDtypeStruct((4, 128, 9), f32)
        assert spec_for_sketch(rules, "ffn_h", "x", trip) == \\
            P(None, ("model", "pod", "data"), None)
        assert spec_for_sketch(rules, "ffn_in", "y", trip) == \\
            P(None, ("pod", "data"), None)
        assert spec_for_sketch(rules, "res", "z", trip) == \\
            P(None, ("pod", "data"), None)
        # non-divisible width: members drop back-to-front (TP alignment
        # survives longest), fully indivisible -> replicated
        odd = jax.ShapeDtypeStruct((4, 6, 9), f32)
        assert spec_for_sketch(rules, "ffn_h", "x", odd) == \\
            P(None, "model", None)
        prime = jax.ShapeDtypeStruct((4, 7, 9), f32)
        assert spec_for_sketch(rules, "ffn_h", "x", prime) == \\
            P(None, None, None)
        psi = jax.ShapeDtypeStruct((4, 9), f32)
        assert spec_for_sketch(rules, "ffn_h", "psi", psi) == P()
        proj = jax.ShapeDtypeStruct((16, 9), f32)
        assert spec_for_sketch(rules, None, "upsilon", proj) == \\
            P(("pod", "data"), None)

        # dry-run certification for the two production targets
        for arch in ("gemma3-27b", "mixtral-8x22b"):
            cfg = get_arch(arch)
            run = make_run_config(cfg, SHAPES["train_4k"])
            state = abstract_train_state(cfg, run)
            with use_rules(rules):
                sh = param_shardings(rules, state)
            rep = sketch_sharding_report(state, sh, rules)
            assert rep, arch
            for key, r in rep.items():
                # mlp/heads-axis nodes take TP x dp (8 ways on this
                # mesh); embed-axis nodes take the ZeRO dp axes (4)
                want = 8 if key.split("/")[0] in ("ffn_h", "attn_o") \
                    else 4
                assert r["shards"] == want, (arch, key, r)
            print(arch, "sharded:", len(rep), "triple leaves")
        print("OK")
    """, devices=8)
    assert "OK" in out


def test_per_worker_sketch_memory_matches_closed_form():
    """tree_memory_bytes_per_worker (closed-form, used by the memory
    bench) equals the live accounting of an actual shard: the packed
    triple buffer is exactly ceil(total/W) f32 elements per worker,
    psi + projections replicate."""
    import jax

    from repro.configs import get_arch, reduced
    from repro.models.transformer import SketchSettings
    from repro.sketches import (
        shard_tree, sharded_tree_memory_bytes, tree_memory_bytes,
        tree_memory_bytes_per_worker, tree_wire_spec,
    )
    from repro.train.state import RunConfig, init_train_state

    cfg = reduced(get_arch("tinyllama-1.1b"))
    run = RunConfig(seq_len=16, global_batch=4,
                    sketch=SketchSettings(enabled=True, k_max=9))
    tree = init_train_state(jax.random.PRNGKey(0), cfg, run).sketch
    total = tree_wire_spec(tree).total
    full = tree_memory_bytes(tree)
    rep = tree_memory_bytes_per_worker(tree, dp_shards=1) - total * 4
    for w in (1, 2, 4):
        ssk = shard_tree(tree, w, 0)
        live = sharded_tree_memory_bytes(ssk)
        closed = tree_memory_bytes_per_worker(tree, dp_shards=w)
        assert live == closed, (w, live, closed)
        # the triple buffer is exactly a 1/W tile (ceil for padding)
        assert ssk.flat.size == -(-total // w), w
        # and the per-worker total never exceeds the replicated
        # footprint's triple share plus the replicated psi/proj
        assert live <= -(-full // w) + rep, (w, live, full, rep)


# ---------------------------------------------------------------------------
# ISSUE 9 differential tier: int8 sketch wire end-to-end + the p2 round
# overlapped with the optimizer update (DESIGN.md §14)
# ---------------------------------------------------------------------------


INT8_E2E_CODE = """
    import dataclasses, re, tempfile
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.checkpoint.checkpointer import (
        RESIDUAL_LAYOUT, Checkpointer, gather_per_worker,
        scatter_per_worker)
    from repro.configs import get_arch, reduced
    from repro.data.synthetic import lm_batch
    from repro.models.transformer import SketchSettings
    from repro.optim.compression import CompressionConfig
    from repro.train.state import RunConfig, init_train_state
    from repro.train.step import make_dp_train_step

    STEPS, W = {steps}, 4
    mesh = Mesh(np.array(jax.devices()[:W]), ("data",))
    cfg = reduced(get_arch("tinyllama-1.1b"))      # sketch_mode=backprop
    key = jax.random.PRNGKey(0)

    def mk(layout, wd):
        # int8 END-TO-END: the sketch increments (sketch_wire_dtype)
        # AND the count-sketch table (compression.wire_dtype) — every
        # non-counter segment of the flat wire is quantized
        return RunConfig(
            seq_len=16, global_batch=8, dp_axis_name="data",
            dp_workers=W, warmup_steps=2, total_steps=max(STEPS, 10),
            dp_collective=layout, sketch_wire_dtype=wd,
            compression=CompressionConfig(
                mode="countsketch", cs_rows=5, cs_cols=512, cs_k=256,
                cs_momentum=0.0, wire_dtype=wd),
            sketch=SketchSettings(enabled=True, k_max=9, beta=0.9,
                                  recon_mode="fast"))

    def train(run):
        state = init_train_state(key, cfg, run)
        state = jax.device_put(state, NamedSharding(mesh, P()))
        step = jax.jit(make_dp_train_step(cfg, run, mesh))
        for s in range(STEPS):
            tok, lab = lm_batch(jax.random.fold_in(key, s), 8, 16,
                                cfg.vocab_size)
            state, m = step(state, {{"tokens": tok, "labels": lab}})
        assert np.isfinite(float(m["loss"]))
        return state, float(m["loss"])

    for layout, n_colls in {layouts}:
        s_f32, l_f32 = train(mk(layout, "fp32"))
        s_i8, l_i8 = train(mk(layout, "int8"))
        gap = abs(l_i8 - l_f32)
        print(layout, f"int8 e2e loss gap {{gap:.4f}}")
        assert gap <= 0.05, (layout, l_f32, l_i8)
        # the quantization is ACTIVE: a nonzero residual ledger exists
        err_mass = sum(float(jnp.abs(x).sum()) for x in
                       jax.tree.leaves(s_i8.opt["sketch_err"]))
        assert err_mass > 0.0, layout

        # HLO: quantization is wire-layer only — the collective count
        # must be UNCHANGED vs the fp32 layout (1 fused / 2 overlap)
        run = mk(layout, "int8")
        state = init_train_state(key, cfg, run)
        tok, lab = lm_batch(key, 8, 16, cfg.vocab_size)
        txt = jax.jit(make_dp_train_step(cfg, run, mesh)).lower(
            jax.device_put(state, NamedSharding(mesh, P())),
            {{"tokens": tok, "labels": lab}}).compile().as_text()
        colls = re.findall(
            r"= \\S+ (all-reduce|all-gather|reduce-scatter|"
            r"all-to-all|collective-permute)", txt)
        assert len(colls) == n_colls and \\
            set(colls) == {{"all-reduce"}}, (layout, colls)
        print(layout, "HLO collective count OK", len(colls))

        # per-worker sketch_err checkpoint round-trip: stacked
        # per_worker_v1 layout, bitwise back onto every worker —
        # the outstanding residual mass survives restarts exactly
        stacked = gather_per_worker(s_i8.opt["sketch_err"], mesh,
                                    "data")
        rows = [np.asarray(l) for l in jax.tree.leaves(stacked)]
        assert all(r.shape[0] == W for r in rows)
        assert any(len({{r[w].tobytes() for w in range(W)}}) > 1
                   for r in rows), "ledgers identical across workers"
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d, keep=1)
            ck.save(STEPS, stacked,
                    metadata={{"residual_layout": RESIDUAL_LAYOUT,
                               "dp_workers": W}})
            restored, _ = ck.restore(jax.tree.map(np.asarray, stacked))
        back = scatter_per_worker(
            jax.tree.map(jnp.asarray, restored), mesh, "data")
        again = gather_per_worker(back, mesh, "data")
        for a, b in zip(jax.tree.leaves(stacked),
                        jax.tree.leaves(again)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \\
                "sketch_err round-trip not mass-exact"
        print(layout, "sketch_err checkpoint round-trip OK")
    print("OK")
"""


P2_OVERLAP_CODE = """
    import dataclasses, re
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs import get_arch, reduced
    from repro.data.synthetic import lm_batch
    from repro.models.transformer import SketchSettings
    from repro.optim.compression import CompressionConfig
    from repro.train.state import RunConfig, init_train_state
    from repro.train.step import collective_plan, make_dp_train_step

    STEPS, W = {steps}, 4
    mesh = Mesh(np.array(jax.devices()[:W]), ("data",))
    cfg = reduced(get_arch("tinyllama-1.1b"))
    key = jax.random.PRNGKey(0)
    ccfg = CompressionConfig(mode="countsketch", cs_rows=5,
                             cs_cols=512, cs_k=64, cs_p2=4,
                             cs_momentum=0.0)

    def mk(layout, p2o):
        return RunConfig(
            seq_len=16, global_batch=8, dp_axis_name="data",
            dp_workers=W, warmup_steps=2, total_steps=max(STEPS, 10),
            dp_collective=layout, compression=ccfg, p2_overlap=p2o,
            sketch=SketchSettings(enabled=True, k_max=9, beta=0.9,
                                  recon_mode="fast"))

    for layout, n_colls in {layouts}:
        outs = {{}}
        for p2o in (False, True):
            run = mk(layout, p2o)
            state = init_train_state(key, cfg, run)
            state = jax.device_put(state, NamedSharding(mesh, P()))
            step = jax.jit(make_dp_train_step(cfg, run, mesh))
            for s in range(STEPS):
                tok, lab = lm_batch(jax.random.fold_in(key, s), 8, 16,
                                    cfg.vocab_size)
                state, m = step(state, {{"tokens": tok,
                                         "labels": lab}})
            outs[p2o] = (state, m)
        # the optimizer-update/p2 interleave is BITWISE the serial
        # nominate -> psum -> complete -> adamw reference: full train
        # state AND metrics (grad_norm included — the sparse update's
        # global_norm reduces in the serial leaf order)
        for x, y in zip(jax.tree.leaves(outs[False]),
                        jax.tree.leaves(outs[True])):
            assert np.array_equal(np.asarray(x), np.asarray(y)), \\
                (layout, "p2 overlap diverged from serial")
        print(layout, "p2 overlap bitwise vs serial OK")

        # structural plan records the overlap; the compiled programs
        # hold the SAME all-reduce count (the p2 round is hidden
        # behind the zero-grad dense pass, not added or removed;
        # the issue-point barrier itself is elided from post-opt CPU
        # HLO text, so bitwise + counts + plan flag are the contract)
        plan = collective_plan(cfg, mk(layout, True),
                               mesh_shape=dict(mesh.shape))
        assert plan["p2_overlap"] is True, plan
        assert collective_plan(
            cfg, mk(layout, False),
            mesh_shape=dict(mesh.shape))["p2_overlap"] is False
        tok, lab = lm_batch(key, 8, 16, cfg.vocab_size)
        batch = {{"tokens": tok, "labels": lab}}
        txts = {{}}
        for p2o in (False, True):
            run = mk(layout, p2o)
            state = init_train_state(key, cfg, run)
            txts[p2o] = jax.jit(
                make_dp_train_step(cfg, run, mesh)).lower(
                jax.device_put(state, NamedSharding(mesh, P())),
                batch).compile().as_text()
        for p2o, txt in txts.items():
            colls = re.findall(
                r"= \\S+ (all-reduce|all-gather|reduce-scatter|"
                r"all-to-all|collective-permute)", txt)
            assert len(colls) == n_colls and \\
                set(colls) == {{"all-reduce"}}, (layout, p2o, colls)
        print(layout, "HLO collective count OK", n_colls)
    print("OK")
"""


@pytest.mark.dp_differential
def test_dp_differential_int8_e2e_w4():
    """ISSUE 9 acceptance (per-PR reduced): int8 END-TO-END on the DP
    wire at W=4 — sketch increments (sketch_wire_dtype) and cs table
    (compression wire_dtype) both int8 — on the fused layout: loss gap
    <= 0.05 vs fp32 over 3 steps, HLO collective count unchanged, and
    the per-worker `sketch_err` ledger survives a checkpoint round-trip
    mass-exactly."""
    out = _run(INT8_E2E_CODE.format(
        steps=3, layouts="(('fused', 1),)"), devices=4)
    assert "OK" in out


@pytest.mark.dp_differential
@pytest.mark.slow
def test_dp_differential_int8_e2e_overlap_w4():
    """ISSUE 9 acceptance (nightly): the int8 e2e contract on the
    two-phase overlap layout (2 collectives: early int8 sketch psum +
    late wire psum carrying the int8 table)."""
    out = _run(INT8_E2E_CODE.format(
        steps=3, layouts="(('overlap', 2),)"), devices=4)
    assert "OK" in out


@pytest.mark.dp_differential
def test_dp_differential_p2_overlap_bitwise_w4():
    """ISSUE 9c acceptance (per-PR reduced): with cs_p2 > 0 on the
    fused layout, overlapping the p2 exact-value round with the
    zero-grad dense AdamW pass is BITWISE the serial reference over 3
    steps (state + metrics), with the same HLO all-reduce count and
    the plan recording p2_overlap."""
    out = _run(P2_OVERLAP_CODE.format(
        steps=3, layouts="(('fused', 2),)"), devices=4)
    assert "OK" in out


@pytest.mark.dp_differential
@pytest.mark.slow
def test_dp_overlap_layout_p2_overlap_bitwise_w4():
    """ISSUE 9c acceptance (nightly): the p2/optimizer interleave on
    the overlap layout (3 all-reduces: early sketch + late wire + p2)
    — bitwise the serial reference."""
    out = _run(P2_OVERLAP_CODE.format(
        steps=3, layouts="(('overlap', 3),)"), devices=4)
    assert "OK" in out


# ---------------------------------------------------------------------------
# DESIGN.md §15 node families under DP / expert sharding
# ---------------------------------------------------------------------------

FAMILY_DP_CODE = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs import get_arch, reduced
    from repro.data.synthetic import lm_batch
    from repro.models.transformer import SketchSettings
    from repro.train.state import RunConfig, init_train_state
    from repro.train.step import make_dp_train_step

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    cfg = reduced(get_arch({arch!r}))
    key = jax.random.PRNGKey(0)
    states = {{}}
    for mode in ("per_node", "overlap", "fused"):
        run = RunConfig(seq_len=16, global_batch=8, dp_axis_name="data",
                        dp_workers=4, dp_collective=mode,
                        warmup_steps=1, total_steps=40,
                        sketch=SketchSettings(enabled=True, k_max=9,
                                              beta=0.9, recon_mode="fast"))
        state = init_train_state(key, cfg, run)
        state = jax.device_put(state, NamedSharding(mesh, P()))
        step = jax.jit(make_dp_train_step(cfg, run, mesh))
        for s in range(3):
            tokens, labels = lm_batch(jax.random.fold_in(key, s), 8, 16,
                                      cfg.vocab_size)
            state, m = step(state, {{"tokens": tokens, "labels": labels}})
        states[mode] = (state, m)
    # overlap consumes THIS step's merged triple (phase 2), so it is
    # bitwise vs per_node for every family — consumed trees included
    strict = ("overlap", "fused") if {all_monitor} else ("overlap",)
    for mode in strict:
        ref, got = states["per_node"], states[mode]
        for a, b in zip(jax.tree.leaves(ref[0].sketch),
                        jax.tree.leaves(got[0].sketch)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), mode
        assert float(ref[1]["loss"]) == float(got[1]["loss"]), mode
    # fused on a CONSUMED tree has the documented one-step consumption
    # lag (sketched backward reads the previous step's merged triple) —
    # tolerance contract, same as the LM lag test above
    gap = abs(float(states["per_node"][1]["loss"]) -
              float(states["fused"][1]["loss"]))
    assert gap <= 0.05, gap
    print("OK")
"""


@pytest.mark.dp_differential
def test_dp_differential_moe_w4():
    """ISSUE 10 acceptance (per-PR reduced): the MoE family's per-expert
    sketch increments stay per-expert-linear, so the overlap two-phase
    merge is BITWISE the per_node psum at W=4 over 3 steps — expert_in
    (L, E, d, k) stacks included. qwen3-moe consumes attn_o (sketched
    backprop on the attention out-projection), so fused keeps the
    documented one-step consumption lag: loss-gap contract instead."""
    out = _run(FAMILY_DP_CODE.format(arch="qwen3-moe-30b-a3b",
                                     all_monitor=False), devices=4)
    assert "OK" in out


@pytest.mark.dp_differential
def test_dp_differential_recurrent_w4():
    """ISSUE 10 acceptance (per-PR reduced): the recurrent family —
    RG-LRU carry nodes ride the kind-bound position-restricted stacks,
    updating exactly once per step, so the dp_defer uniformity invariant
    holds and overlap agrees bitwise with per_node at W=4 (the FFN
    nodes are consumed, so fused is the loss-gap lag contract)."""
    out = _run(FAMILY_DP_CODE.format(arch="recurrentgemma-2b",
                                     all_monitor=False), devices=4)
    assert "OK" in out


@pytest.mark.dp_differential
@pytest.mark.slow
def test_dp_differential_xlstm_monitor_only_w4():
    """xlstm's carry nodes are ALL monitor-only — no sketched-backprop
    consumer, no consumption lag — so every DP layout (per_node /
    overlap / fused) must be bitwise-identical at W=4 over 3 steps."""
    out = _run(FAMILY_DP_CODE.format(arch="xlstm-1.3b",
                                     all_monitor=True), devices=4)
    assert "OK" in out


@pytest.mark.dp_differential
def test_expert_sharded_sketch_state_bitwise_w4():
    """ISSUE 10 acceptance: expert-axis sharding of the per-expert
    sketch state is exact — the vmapped per-expert update partitioned
    over 4 devices (each owning its local experts, per
    `spec_for_sketch`'s expert-axis rule) is BITWISE the unsharded
    update of the same (E, d, k) stack against the same dispatch slab."""
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.models.transformer import SketchSettings, \\
            _update_expert_triple
        from repro.sketches import init_node_tree
        from repro.sketches.tree import NodeSpec

        E, d, T, k = 4, 16, 32, 9
        tree = init_node_tree(
            jax.random.PRNGKey(0),
            {"expert_in": NodeSpec(width=d, layers=E, kind="paper")},
            num_tokens=T, k_max=k)
        node = tree.nodes["expert_in"]
        xg = jax.random.normal(jax.random.PRNGKey(1), (E, 24, d))
        st = SketchSettings(enabled=True, k_max=k, beta=0.9,
                            recon_mode="fast")

        def upd(node, xg):
            return _update_expert_triple(node, xg, tree.proj, k, st)

        ref = jax.jit(upd)(node, xg)

        mesh = Mesh(np.array(jax.devices()[:4]), ("model",))
        ex = NamedSharding(mesh, P("model"))       # expert dim sharded
        node_sh = dataclasses.replace(
            node,
            x=jax.device_put(node.x, ex), y=jax.device_put(node.y, ex),
            z=jax.device_put(node.z, ex),
            psi=jax.device_put(node.psi, ex))
        got = jax.jit(upd)(node_sh, jax.device_put(xg, ex))
        for f in ("x", "y", "z"):
            a = np.asarray(getattr(ref, f))
            b = np.asarray(getattr(got, f))
            assert np.array_equal(a, b), f
            # each device owns exactly its local expert's rows
        assert got.x.sharding.spec == P("model")
        print("OK")
    """, devices=4)
    assert "OK" in out
