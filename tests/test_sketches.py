"""The unified sketches/ subsystem (ISSUE 3): canonical-update parity
(jnp vs fused Pallas kernel, mixed dtypes), NodeTree registry semantics,
rank-change refresh without recompilation, checkpoint round-trip +
legacy-layout migration, and fixed-seed loss parity with the
pre-refactor implementations."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sketch import Projections, SketchConfig, \
    sketch_update_single
from repro.sketches import (
    NodeSpec, NodeTree, SketchNode, ema_triple_update, init_node_tree,
    legacy_layout, node_paths, refresh_tree, restore_legacy_state,
    zero_sketches,
)


def _proj(key, T, k):
    ks = jax.random.split(key, 4)
    return Projections(
        upsilon=jax.random.normal(ks[0], (T, k)),
        omega=jax.random.normal(ks[1], (T, k)),
        phi=jax.random.normal(ks[2], (T, k)),
        psi=jax.random.normal(ks[3], (1, k)),
    )


# ---------------------------------------------------------------------------
# Canonical update: fused Pallas kernel vs sketch_update_single, mixed
# dtypes (the production-forward routing satellite)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("T,d,k", [(64, 48, 9), (130, 96, 7),
                                   (256, 128, 33)])
@pytest.mark.parametrize("act_dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_update_matches_single(rng, T, d, k, act_dtype):
    ks = jax.random.split(rng, 5)
    a = jax.random.normal(ks[0], (T, d), act_dtype)
    x = jax.random.normal(ks[1], (d, k))
    y = jax.random.normal(ks[2], (d, k))
    z = jax.random.normal(ks[3], (d, k))
    proj = _proj(ks[4], T, k)
    ka = jnp.asarray(k)
    want = sketch_update_single(x, y, z, a, a, proj, 0, 0.9, ka)
    got = ema_triple_update(x, y, z, a, proj.upsilon, proj.omega,
                            proj.phi, proj.psi[0], 0.9, ka,
                            use_kernel=True)
    tol = 1e-5 if act_dtype == jnp.float32 else 5e-2
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=tol, rtol=tol)


def test_kernel_update_respects_rank_mask(rng):
    """Masked columns stay exactly zero through the kernel path too."""
    T, d, k = 64, 32, 9
    ks = jax.random.split(rng, 2)
    a = jax.random.normal(ks[0], (T, d))
    zeros = jnp.zeros((d, k))
    proj = _proj(ks[1], T, k)
    ka = jnp.asarray(5)
    got = ema_triple_update(zeros, zeros, zeros, a, proj.upsilon,
                            proj.omega, proj.phi, proj.psi[0], 0.9, ka,
                            use_kernel=True)
    for g in got:
        assert float(jnp.abs(g[:, 5:]).max()) == 0.0
        assert float(jnp.abs(g[:, :5]).max()) > 0.0


def test_production_forward_routes_through_kernel(rng):
    """`use_pallas(True)` swaps the transformer forward's EMA updates
    onto the fused kernel; sketch results must match the jnp path."""
    from repro.configs import get_arch, reduced
    from repro.kernels.ops import pallas_enabled, use_pallas
    from repro.models.transformer import (
        SketchSettings, forward, init_lm_sketch_state, init_params,
    )

    cfg = reduced(get_arch("tinyllama-1.1b"))
    params = init_params(rng, cfg)
    st = SketchSettings(enabled=True, k_max=9, beta=0.9)
    B, S = 2, 16
    sketch = init_lm_sketch_state(jax.random.fold_in(rng, 1), cfg, st,
                                  B * S)
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)

    ref = forward(params, tokens, cfg=cfg, mode="train",
                  sketch_state=sketch, settings=st)
    assert not pallas_enabled()
    use_pallas(True)
    try:
        ker = forward(params, tokens, cfg=cfg, mode="train",
                      sketch_state=sketch, settings=st)
    finally:
        use_pallas(False)
    np.testing.assert_allclose(
        np.asarray(ker["logits"], np.float32),
        np.asarray(ref["logits"], np.float32), atol=1e-5, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(ref["sketch_state"]),
                    jax.tree.leaves(ker["sketch_state"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# NodeTree registry semantics
# ---------------------------------------------------------------------------


def _tree(key, T=32, k_max=9):
    specs = {"ffn_in": NodeSpec(width=16, layers=3),
             "res": NodeSpec(width=8, layers=3),
             "solo": NodeSpec(width=12)}
    return init_node_tree(key, specs, T, k_max)


def test_node_tree_registration_and_paths(rng):
    tree = _tree(rng)
    assert tree.nodes["ffn_in"].x.shape == (3, 16, 9)
    assert tree.nodes["solo"].x.shape == (12, 9)
    assert int(tree.rank) == 4
    paths = node_paths(tree)
    assert paths == ["block0/ffn_in", "block1/ffn_in", "block2/ffn_in",
                     "res/0", "res/1", "res/2", "solo"]


def test_refresh_tree_new_projections_same_shapes(rng):
    tree = _tree(rng)
    # dirty the sketches so the zeroing is observable
    tree = dataclasses.replace(
        tree, nodes={n: dataclasses.replace(v, x=v.x + 1.0)
                     for n, v in tree.nodes.items()})
    tree2 = refresh_tree(tree)
    assert int(tree2.epoch) == 1
    assert int(tree2.step) == 0
    for n in tree.nodes:
        assert tree2.nodes[n].x.shape == tree.nodes[n].x.shape
        assert float(jnp.abs(tree2.nodes[n].x).max()) == 0.0
        assert not np.allclose(np.asarray(tree2.nodes[n].psi),
                               np.asarray(tree.nodes[n].psi))
    assert not np.allclose(np.asarray(tree2.proj["upsilon"]),
                           np.asarray(tree.proj["upsilon"]))
    # deterministic: refreshing the same tree yields the same values
    tree3 = refresh_tree(tree)
    np.testing.assert_array_equal(np.asarray(tree3.proj["omega"]),
                                  np.asarray(tree2.proj["omega"]))


def test_zero_sketches_keeps_psi(rng):
    tree = _tree(rng)
    tree = dataclasses.replace(
        tree, nodes={n: dataclasses.replace(v, y=v.y + 2.0)
                     for n, v in tree.nodes.items()})
    z = zero_sketches(tree)
    for n in tree.nodes:
        assert float(jnp.abs(z.nodes[n].y).max()) == 0.0
        np.testing.assert_array_equal(np.asarray(z.nodes[n].psi),
                                      np.asarray(tree.nodes[n].psi))


def test_node_kind_validated():
    with pytest.raises(ValueError, match="kind"):
        SketchNode(x=jnp.zeros((2, 3)), y=jnp.zeros((2, 3)),
                   z=jnp.zeros((2, 3)), psi=jnp.zeros((3,)),
                   kind="banana")


# ---------------------------------------------------------------------------
# Rank change + projection refresh with ZERO extra jit compilations
# ---------------------------------------------------------------------------


def test_rank_change_refresh_never_recompiles(rng):
    from repro.configs import get_arch, reduced
    from repro.models.transformer import SketchSettings
    from repro.train.loop import refresh_sketch_tree
    from repro.train.state import RunConfig, init_train_state
    from repro.train.step import make_train_step

    cfg = reduced(get_arch("tinyllama-1.1b"))
    run = RunConfig(seq_len=16, global_batch=2,
                    sketch=SketchSettings(enabled=True, k_max=9,
                                          beta=0.9, recon_mode="fast"),
                    warmup_steps=2, total_steps=40)
    state = init_train_state(rng, cfg, run)
    step = jax.jit(make_train_step(cfg, run))
    tokens = jax.random.randint(rng, (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}

    state, _ = step(state, batch)
    state, _ = step(state, batch)
    # production-loop rank change: new rank scalar + fold_in refresh
    old_rank = int(state.sketch.rank)
    sketch = dataclasses.replace(state.sketch,
                                 rank=state.sketch.rank - 1)
    sketch = refresh_sketch_tree(sketch)
    assert int(sketch.epoch) == 1 and int(sketch.rank) == old_rank - 1
    state = dataclasses.replace(state, sketch=sketch)
    state, metrics = step(state, batch)
    state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    # the static-shape contract: ONE compilation each, rank change or not
    assert step._cache_size() == 1
    assert refresh_sketch_tree._cache_size() == 1


def test_donated_train_step_with_sketches(rng):
    """Regression: the NodeTree init must allocate x/y/z as distinct
    buffers — aliasing one zeros array across the triple made
    `jit(donate_argnums=(0,))` fail with 'donate the same buffer twice'
    in the production loop."""
    from repro.configs import get_arch, reduced
    from repro.models.transformer import SketchSettings
    from repro.train.state import RunConfig, init_train_state
    from repro.train.step import make_train_step

    cfg = reduced(get_arch("tinyllama-1.1b"))
    run = RunConfig(seq_len=16, global_batch=2,
                    sketch=SketchSettings(enabled=True, k_max=9,
                                          beta=0.9, recon_mode="fast"))
    state = init_train_state(rng, cfg, run)
    step = jax.jit(make_train_step(cfg, run), donate_argnums=(0,))
    tokens = jax.random.randint(rng, (2, 16), 0, cfg.vocab_size)
    state, metrics = step(state, {"tokens": tokens,
                                  "labels": jnp.roll(tokens, -1, 1)})
    assert bool(jnp.isfinite(metrics["loss"]))


# ---------------------------------------------------------------------------
# Checkpoint round-trip + legacy per-group-dict migration
# ---------------------------------------------------------------------------


def _lm_state(rng):
    from repro.configs import get_arch, reduced
    from repro.models.transformer import SketchSettings
    from repro.train.state import RunConfig, init_train_state

    cfg = reduced(get_arch("tinyllama-1.1b"))
    run = RunConfig(seq_len=16, global_batch=2,
                    sketch=SketchSettings(enabled=True, k_max=9,
                                          beta=0.9, recon_mode="fast"))
    return init_train_state(rng, cfg, run)


def test_checkpoint_roundtrip_nodetree(rng, tmp_path):
    from repro.checkpoint.checkpointer import Checkpointer

    state = _lm_state(rng)
    # make the sketch non-trivial so equality is meaningful
    state = dataclasses.replace(
        state, sketch=dataclasses.replace(
            state.sketch,
            nodes={n: dataclasses.replace(v, x=v.x + 3.0)
                   for n, v in state.sketch.nodes.items()}))
    ckpt = Checkpointer(str(tmp_path))
    ckpt.save(1, state)
    template = _lm_state(jax.random.fold_in(rng, 9))
    restored, meta = ckpt.restore(template)
    assert meta["sketch_layout"] == "nodetree-v1"
    assert isinstance(restored.sketch, NodeTree)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_migrates_legacy_dict_layout(rng, tmp_path):
    """A checkpoint written with the PR 0-2 per-group dict sketch layout
    must restore into today's NodeTree without error."""
    from repro.checkpoint.checkpointer import Checkpointer

    from repro.core.monitor import MonitorState, monitor_record

    state = _lm_state(rng)
    tree = dataclasses.replace(
        state.sketch,
        nodes={n: dataclasses.replace(v, z=v.z - 1.5)
               for n, v in state.sketch.nodes.items()})
    # legacy writers recorded monitor rows in a different (and across
    # checkpoint generations, inconsistent) row order — fill the ring so
    # the migration's reset is observable
    dirty_monitor = monitor_record(
        state.monitor, jnp.ones(state.monitor.buffer.shape[1:]))
    legacy_state = dataclasses.replace(state, sketch=legacy_layout(tree),
                                       monitor=dirty_monitor)
    ckpt = Checkpointer(str(tmp_path))
    ckpt.save(7, legacy_state)

    template = dataclasses.replace(_lm_state(jax.random.fold_in(rng, 3)),
                                   sketch=tree)
    restored, _ = ckpt.restore(template)
    assert isinstance(restored.sketch, NodeTree)
    for name, node in tree.nodes.items():
        got = restored.sketch.nodes[name]
        np.testing.assert_array_equal(np.asarray(got.z),
                                      np.asarray(node.z))
        np.testing.assert_array_equal(np.asarray(got.psi),
                                      np.asarray(node.psi))
    np.testing.assert_array_equal(np.asarray(restored.sketch.rank),
                                  np.asarray(tree.rank))
    # params restored positionally as usual
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the monitor ring is RESET on migration (legacy row order is not
    # the tree_metrics/node_paths order; stale rows would interleave
    # different layers' histories in one windowed statistic)
    assert isinstance(restored.monitor, MonitorState)
    assert float(np.abs(np.asarray(restored.monitor.buffer)).max()) == 0.0
    assert int(restored.monitor.count) == 0 and \
        int(restored.monitor.idx) == 0


def test_restore_legacy_rejects_unknown_layout(rng):
    state = _lm_state(rng)
    leaves = jax.tree.leaves(state)
    with pytest.raises(ValueError, match="not a known sketch layout"):
        restore_legacy_state(state, leaves[:-5])


# ---------------------------------------------------------------------------
# Fixed-seed loss parity with the pre-refactor implementations
# (baselines captured at commit d856e56, immediately before the
# NodeTree unification; acceptance bar is 1e-5)
# ---------------------------------------------------------------------------

MLP_BASELINES = {
    "standard": [0.68862885, 0.88423091, 0.64984298, 0.67808133,
                 0.72123283],
    "sketched_fixed": [1.13031101, 1.47688556, 1.26603627, 1.14640212,
                       1.47115064],
    "monitor": [0.68862885, 0.88423091, 0.64984298, 0.67808133,
                0.72123283],
    "corange": [1.01348257, 1.38370824, 1.06524229, 1.04804766,
                1.23942566],
}


@pytest.mark.parametrize("variant", sorted(MLP_BASELINES))
def test_mlp_variant_losses_match_prerefactor(variant):
    from repro.configs.paper import MLPConfig
    from repro.data.synthetic import class_prototypes, \
        classification_batch
    from repro.train.paper_trainer import train

    cfg = MLPConfig(name="t", d_in=32, d_hidden=48, d_out=4,
                    num_hidden_layers=3, activation="tanh",
                    batch_size=32, learning_rate=2e-3)
    scfg = SketchConfig(rank=3, max_rank=6, beta=0.9, batch_size=32,
                        recon_mode="fast")
    key = jax.random.PRNGKey(50)
    protos = class_prototypes(key, cfg.d_out, cfg.d_in)
    batch_fn = lambda k: classification_batch(k, protos, cfg.batch_size,
                                              1.0)
    res = train(cfg, scfg, variant, steps=25, batch_fn=batch_fn, seed=0)
    got = [h["loss"] for h in res.history][-5:]
    np.testing.assert_allclose(got, MLP_BASELINES[variant], atol=1e-5)


LM_BASELINE = [6.21930933, 5.90786457, 6.29168558, 5.9376874,
               5.95809937, 6.13845921]


def test_lm_train_step_losses_match_prerefactor():
    from repro.configs import get_arch, reduced
    from repro.data.pipeline import PipelineConfig, host_batch
    from repro.models.transformer import SketchSettings
    from repro.train.state import RunConfig, init_train_state
    from repro.train.step import make_train_step

    cfg = reduced(get_arch("tinyllama-1.1b"))
    run = RunConfig(seq_len=16, global_batch=2,
                    sketch=SketchSettings(enabled=True, k_max=9,
                                          beta=0.9, recon_mode="fast"),
                    warmup_steps=2, total_steps=40)
    state = init_train_state(jax.random.PRNGKey(0), cfg, run)
    step = jax.jit(make_train_step(cfg, run))
    pipe = PipelineConfig(seed=0, global_batch=2, seq_len=16,
                          vocab=cfg.vocab_size)
    got = []
    for s in range(len(LM_BASELINE)):
        tokens, labels = host_batch(pipe, s)
        state, m = step(state, {"tokens": tokens, "labels": labels})
        got.append(float(m["loss"]))
    np.testing.assert_allclose(got, LM_BASELINE, atol=1e-5)


# ---------------------------------------------------------------------------
# p-sparsified projections (DESIGN.md §13): fixed-seed pins + loss
# parity vs the dense gaussian runs at the same seed and matched rank
# ---------------------------------------------------------------------------

# standard/monitor trees have no loss consumer, so their psparse runs
# are BITWISE the dense runs — pinned to the same values
MLP_PSPARSE_BASELINES = {
    "standard": MLP_BASELINES["standard"],
    "sketched_fixed": [1.20343637, 1.39826918, 1.44148183, 1.21301603,
                       1.52499294],
    "monitor": MLP_BASELINES["monitor"],
    "corange": [1.02881241, 1.32731891, 1.12212873, 1.0347501,
                1.24137247],
}


def _mlp_psparse_setup():
    from repro.configs.paper import MLPConfig
    from repro.data.synthetic import class_prototypes, \
        classification_batch

    cfg = MLPConfig(name="t", d_in=32, d_hidden=48, d_out=4,
                    num_hidden_layers=3, activation="tanh",
                    batch_size=32, learning_rate=2e-3)
    protos = class_prototypes(jax.random.PRNGKey(50), cfg.d_out,
                              cfg.d_in)
    batch_fn = lambda k: classification_batch(k, protos, cfg.batch_size,
                                              1.0)
    scfg = SketchConfig(rank=3, max_rank=6, beta=0.9, batch_size=32,
                        recon_mode="fast", proj_kind="psparse",
                        proj_density=0.1)
    return cfg, scfg, batch_fn


@pytest.mark.parametrize("variant", sorted(MLP_PSPARSE_BASELINES))
def test_mlp_psparse_variant_losses_pinned(variant):
    from repro.train.paper_trainer import train

    cfg, scfg, batch_fn = _mlp_psparse_setup()
    res = train(cfg, scfg, variant, steps=25, batch_fn=batch_fn, seed=0)
    got = [h["loss"] for h in res.history][-5:]
    np.testing.assert_allclose(got, MLP_PSPARSE_BASELINES[variant],
                               atol=1e-5)


# mean of the last-50 losses of the 100-step GAUSSIAN runs at this
# seed (the parity anchors; per-step losses are batch-noisy, the
# 50-step mean is stable to ~0.01)
MLP_DENSE_MEAN50 = {"sketched_fixed": 0.78249148, "corange": 0.58867262}


@pytest.mark.parametrize("variant", sorted(MLP_DENSE_MEAN50))
def test_mlp_psparse_loss_parity(variant):
    """Acceptance bar: psparse training at density 0.1 stays within
    0.05 of the dense gaussian loss at matched rank (the two
    sketch-CONSUMING variants; standard/monitor are trivially
    bitwise-equal and pinned above)."""
    from repro.train.paper_trainer import train

    cfg, scfg, batch_fn = _mlp_psparse_setup()
    res = train(cfg, scfg, variant, steps=100, batch_fn=batch_fn,
                seed=0)
    mean50 = float(np.mean([h["loss"] for h in res.history][-50:]))
    gap = abs(mean50 - MLP_DENSE_MEAN50[variant])
    assert gap <= 0.05, (variant, mean50, gap)


LM_PSPARSE_BASELINE = [6.21930933, 5.90786457, 6.291852, 5.93683529,
                       5.95633411, 6.13756943]


def test_lm_psparse_losses_pinned_and_parity():
    """Sketched LM with psparse projections: losses pinned at the same
    tolerance as the dense baseline, and every step within 0.05 of the
    dense LM_BASELINE (sketched backprop consumes the reconstruction,
    so the curves differ — by under 0.002 in practice)."""
    from repro.configs import get_arch, reduced
    from repro.data.pipeline import PipelineConfig, host_batch
    from repro.models.transformer import SketchSettings
    from repro.train.state import RunConfig, init_train_state
    from repro.train.step import make_train_step

    cfg = reduced(get_arch("tinyllama-1.1b"))
    run = RunConfig(seq_len=16, global_batch=2,
                    sketch=SketchSettings(enabled=True, k_max=9,
                                          beta=0.9, recon_mode="fast",
                                          proj_kind="psparse"),
                    warmup_steps=2, total_steps=40)
    state = init_train_state(jax.random.PRNGKey(0), cfg, run)
    step = jax.jit(make_train_step(cfg, run))
    pipe = PipelineConfig(seed=0, global_batch=2, seq_len=16,
                          vocab=cfg.vocab_size)
    got = []
    for s in range(len(LM_PSPARSE_BASELINE)):
        tokens, labels = host_batch(pipe, s)
        state, m = step(state, {"tokens": tokens, "labels": labels})
        got.append(float(m["loss"]))
    np.testing.assert_allclose(got, LM_PSPARSE_BASELINE, atol=1e-5)
    gaps = np.abs(np.array(got) - np.array(LM_BASELINE))
    assert gaps.max() <= 0.05, gaps


# ---------------------------------------------------------------------------
# One-EMA-implementation invariant (acceptance criterion): the EMA
# recurrence exists only under sketches/ and kernels/
# ---------------------------------------------------------------------------


def test_single_ema_implementation():
    import os
    import re

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src", "repro")
    pat = re.compile(r"beta \* \w+ \+ \(1\.?0? - beta\)")
    offenders = []
    for dirpath, _, files in os.walk(root):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            rel = os.path.relpath(path, root)
            if rel.startswith(("sketches", "kernels")):
                continue
            with open(path) as fh:
                if pat.search(fh.read()):
                    offenders.append(rel)
    assert not offenders, (
        f"EMA update math re-inlined outside sketches//kernels/: "
        f"{offenders}")


def test_increment_apply_decomposition_matches_update(rng):
    """The fused-DP decomposition ema_apply_increment(x,
    ema_triple_increment(...)) must reproduce the per_node DP-exact
    path ema_triple_update(..., axis_name=ax) bitwise — checked at W=1
    (psum identity) under a 1-device shard_map, for both the jnp and
    the Pallas kernel branch. (The axis-FREE kernel update fuses the
    EMA accumulate inside the kernel — a different rounding order —
    which is why the per_node axis path, the thing the fused layout
    actually replaces, is the reference.)"""
    import functools

    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.sketches.update import (
        ema_apply_increment, ema_triple_increment, ema_triple_update,
    )

    T, d, k = 24, 16, 9
    ks = jax.random.split(rng, 6)
    a = jax.random.normal(ks[0], (T, d))
    ups, omg, phi = (jax.random.normal(ks[i], (T, k)) for i in (1, 2, 3))
    psi = jax.random.normal(ks[4], (k,))
    x0, y0, z0 = (0.3 * jax.random.normal(jax.random.fold_in(ks[5], i),
                                          (d, k)) for i in range(3))
    ka = jnp.asarray(7)
    beta = 0.9
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

    for use_kernel in (False, True):
        upd = functools.partial(
            ema_triple_update, upsilon=ups, omega=omg, phi=phi, psi=psi,
            beta=beta, k_active=ka, axis_name="data",
            use_kernel=use_kernel)
        want = jax.jit(shard_map(
            lambda aa: upd(x0, y0, z0, a=aa), mesh=mesh,
            in_specs=P("data"), out_specs=P(), check_vma=False))(a)
        incs = ema_triple_increment(x0, y0, z0, a, ups, omg, phi, psi,
                                    beta, ka, use_kernel=use_kernel)
        got = [ema_apply_increment(s, i, beta, ka)
               for s, i in zip((x0, y0, z0), incs)]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=f"kernel={use_kernel}")
