"""Count-sketch gradient compression subsystem (ISSUE 1 gates).

Covers: Pallas csvec_insert vs jnp reference parity (interpret mode),
sketch LINEARITY (W-worker merge == sketch of summed gradients — exact
on integer-valued grads where float addition is associative), error-
feedback mass conservation, heavy-hitter recovery on a heavy-tailed
vector, and the countsketch train-step path end to end.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.countsketch import (
    insert, insert_at, make_csvec, merge, query, query_all, table_bytes,
    topk_streaming, unsketch, zero_table,
)
from repro.kernels.csvec_insert import csvec_insert
from repro.kernels.csvec_topk import csvec_topk
from repro.kernels.ref import csvec_insert_ref, csvec_topk_ref
from repro.optim.compression import (
    CompressionConfig, compressed_bytes, resolve_countsketch,
)
from repro.optim.sketched_sgd import (
    compress_grads_countsketch, flat_dim, init_countsketch_state,
)
from repro.parallel.collectives import merge_csvecs


# -- kernel vs reference parity ----------------------------------------------


@pytest.mark.parametrize("dim,rows,cols,blk", [
    (1000, 3, 128, 512),       # dim < blk after clamping
    (5000, 5, 256, 1024),      # ragged final block
    (70000, 3, 512, 2048),     # many blocks
    (4096, 7, 1024, 2048),     # wide table, exact block multiple
])
def test_csvec_insert_kernel_matches_ref(rng, dim, rows, cols, blk):
    cs = make_csvec(rng, dim=dim, rows=rows, cols=cols)
    v = jax.random.normal(jax.random.fold_in(rng, dim), (dim,))
    want = csvec_insert_ref(cs.table, cs.params, v)
    got = csvec_insert(cs.table, cs.params, v, blk=blk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_csvec_insert_accumulates_onto_existing_table(rng):
    cs = make_csvec(rng, dim=2000, rows=3, cols=256)
    v1 = jax.random.normal(jax.random.fold_in(rng, 1), (2000,))
    v2 = jax.random.normal(jax.random.fold_in(rng, 2), (2000,))
    t1 = csvec_insert(cs.table, cs.params, v1)
    t12 = csvec_insert(t1, cs.params, v2)
    want = insert(insert(cs, v1), v2).table
    np.testing.assert_allclose(np.asarray(t12), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# -- linearity / mergeable collectives ---------------------------------------


def test_merge_of_worker_sketches_is_sketch_of_sum_exact(rng):
    """W-worker merged sketch bitwise-matches the single sketch of the
    summed gradients. Integer-valued grads make float addition exact, so
    the linearity identity holds BITWISE, not just approximately."""
    W, dim = 4, 10000
    cs0 = make_csvec(rng, dim=dim, rows=5, cols=512)
    grads = [
        jax.random.randint(jax.random.fold_in(rng, w), (dim,), -64, 64
                           ).astype(jnp.float32)
        for w in range(W)
    ]
    merged = merge_csvecs([insert(cs0, g) for g in grads])
    single = insert(cs0, sum(grads))
    np.testing.assert_array_equal(np.asarray(merged.table),
                                  np.asarray(single.table))


def test_merge_linearity_float_close(rng):
    """Same identity on arbitrary float grads: exact up to float
    summation order."""
    W, dim = 3, 8192
    cs0 = make_csvec(rng, dim=dim, rows=3, cols=256)
    grads = [jax.random.normal(jax.random.fold_in(rng, w), (dim,))
             for w in range(W)]
    merged = merge_csvecs([insert(cs0, g) for g in grads])
    single = insert(cs0, sum(grads))
    np.testing.assert_allclose(np.asarray(merged.table),
                               np.asarray(single.table),
                               atol=1e-4, rtol=1e-5)


def test_merge_rejects_mismatched_geometry(rng):
    a = make_csvec(rng, dim=100, rows=3, cols=128)
    b = make_csvec(rng, dim=100, rows=5, cols=128)
    with pytest.raises(ValueError):
        merge(a, b)


def test_query_is_unbiased_scale(rng):
    """Median-of-r estimates track the true values on a sparse vector
    (few collisions -> near-exact recovery)."""
    dim = 4096
    cs = make_csvec(rng, dim=dim, rows=5, cols=1024)
    idx = jnp.arange(0, dim, 173)
    v = jnp.zeros(dim).at[idx].set(
        jax.random.normal(rng, (idx.shape[0],)) * 10.0)
    est = query(insert(cs, v), idx)
    np.testing.assert_allclose(np.asarray(est), np.asarray(v[idx]),
                               atol=1e-3, rtol=0.3)


# -- heavy hitters ------------------------------------------------------------


def test_heavy_hitter_recovery_heavy_tailed(rng):
    """On a heavy-tailed vector (Zipf-like magnitudes) the top-k by
    |median estimate| recovers most true heavy coordinates."""
    dim, n_heavy = 20000, 20
    cs = make_csvec(rng, dim=dim, rows=5, cols=2048)
    noise = 0.01 * jax.random.normal(rng, (dim,))
    heavy_idx = jax.random.choice(
        jax.random.fold_in(rng, 1), dim, (n_heavy,), replace=False)
    heavy_val = 100.0 / (1 + jnp.arange(n_heavy)) ** 0.8
    sgn = jnp.where(
        jax.random.bernoulli(jax.random.fold_in(rng, 2), 0.5,
                             (n_heavy,)), 1.0, -1.0)
    v = noise.at[heavy_idx].set(heavy_val * sgn)
    rec = unsketch(insert(cs, v), k=2 * n_heavy)
    found = set(np.flatnonzero(np.asarray(rec)).tolist())
    hits = len(found & set(np.asarray(heavy_idx).tolist()))
    assert hits >= int(0.8 * n_heavy), (hits, n_heavy)
    # recovered values approximate the true ones
    got = np.asarray(rec)[np.asarray(heavy_idx)]
    want = np.asarray(v)[np.asarray(heavy_idx)]
    mask = got != 0
    np.testing.assert_allclose(got[mask], want[mask], atol=1.0, rtol=0.2)


# -- streaming heavy-hitter recovery (ISSUE 2 tentpole) -----------------------


@pytest.mark.parametrize("dim,rows,cols,k,chunk", [
    (10000, 5, 1024, 64, 1000),     # ragged tail (dim % chunk != 0)
    (4096, 3, 512, 32, 4096),       # single chunk, exact fit
    (3000, 5, 256, 16, 8192),       # chunk > dim (clamped)
    (8192, 5, 512, 128, 2048),      # exact chunk multiple
    (7001, 7, 512, 64, 512),        # prime dim, many chunks, even r next
    (5000, 4, 256, 32, 1024),       # even r (interpolated median)
])
def test_streaming_topk_matches_dense_oracle(rng, dim, rows, cols, k,
                                             chunk):
    """Candidate selection must match the dense query_all+top_k oracle
    BIT-FOR-BIT across chunk boundaries, tails, and both median
    parities — both for the jnp scan path and the Pallas kernel."""
    cs = make_csvec(rng, dim=dim, rows=rows, cols=cols)
    v = jax.random.normal(jax.random.fold_in(rng, dim), (dim,)) ** 3
    cs = insert(cs, v)
    want_v, want_i = csvec_topk_ref(cs.table, cs.params, dim, k)

    got_v, got_i = topk_streaming(cs, k, chunk=chunk)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))

    ker_v, ker_i = csvec_topk(cs.table, cs.params, dim=dim, k=k,
                              chunk=chunk)
    np.testing.assert_array_equal(np.asarray(ker_i), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(ker_v), np.asarray(want_v),
                               atol=1e-6, rtol=1e-6)


def test_streaming_topk_equals_dense_unsketch(rng):
    """Scattering the streaming (vals, idx) reproduces unsketch exactly."""
    dim, k = 20000, 128
    cs = make_csvec(rng, dim=dim, rows=5, cols=2048)
    cs = insert(cs, jax.random.normal(rng, (dim,)) ** 3)
    vals, idx = topk_streaming(cs, k, chunk=3000)
    rec = jnp.zeros(dim, jnp.float32).at[idx].set(vals)
    np.testing.assert_array_equal(np.asarray(rec),
                                  np.asarray(unsketch(cs, k)))


def _max_intermediate_size(jaxpr) -> int:
    """Largest element count of any value produced inside a jaxpr
    (recursing into scan/cond/call sub-jaxprs)."""
    import jax.core

    worst = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v.aval, "size"):
                worst = max(worst, v.aval.size)
        for p in eqn.params.values():
            sub = []
            if isinstance(p, jax.core.ClosedJaxpr):
                sub = [p.jaxpr]
            elif isinstance(p, jax.core.Jaxpr):
                sub = [p]
            elif isinstance(p, (tuple, list)):
                sub = [q.jaxpr if isinstance(q, jax.core.ClosedJaxpr)
                       else q for q in p
                       if isinstance(q, (jax.core.Jaxpr,
                                         jax.core.ClosedJaxpr))]
            for s in sub:
                worst = max(worst, _max_intermediate_size(s))
    return worst


def test_streaming_recovery_memory_stays_o_chunk_plus_k(rng):
    """The jaxpr of the streaming path must never materialize a
    dim-sized (let alone (r, dim)) intermediate — peak is O(r * chunk +
    k) — while the dense oracle provably does."""
    dim, rows, cols, k, chunk = 1_000_000, 3, 1024, 64, 8192
    cs = make_csvec(rng, dim=dim, rows=rows, cols=cols)

    stream = jax.make_jaxpr(
        lambda t: topk_streaming(
            type(cs)(table=t, params=cs.params, dim=dim), k, chunk=chunk)
    )(cs.table)
    worst = _max_intermediate_size(stream.jaxpr)
    assert worst <= 4 * rows * chunk, worst      # O(chunk), not O(dim)

    dense = jax.make_jaxpr(
        lambda t: unsketch(
            type(cs)(table=t, params=cs.params, dim=dim), k)
    )(cs.table)
    assert _max_intermediate_size(dense.jaxpr) >= rows * dim


@pytest.mark.slow
def test_streaming_topk_at_10m_scale(rng):
    """D = 10M: build the sketch sparsely (insert_at), recover heavy
    hitters streaming, and match the dense oracle's candidate set
    bit-for-bit. The streaming path holds O(chunk + k); only the oracle
    pays the (r, D) dense cost here."""
    dim, n_heavy, k = 10_000_000, 64, 128
    # r=5: at D=10M a median-of-3 admits too many phantom heavy hitters
    # (2-of-3 bucket collisions); 5 rows need 3 collisions -> ~none
    cs = make_csvec(rng, dim=dim, rows=5, cols=16384)
    idx = jax.random.choice(rng, dim, (4 * n_heavy,), replace=False)
    vals = jnp.concatenate([
        100.0 / (1 + jnp.arange(n_heavy)) ** 0.7,
        0.01 * jnp.ones(3 * n_heavy)])
    cs = insert_at(cs, idx, vals)
    got_v, got_i = topk_streaming(cs, k, chunk=262144)
    want_v, want_i = csvec_topk_ref(cs.table, cs.params, dim, k)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    heavy = set(np.asarray(idx[:n_heavy]).tolist())
    hits = len(heavy & set(np.asarray(got_i).tolist()))
    assert hits >= int(0.85 * n_heavy), (hits, n_heavy)


def test_insert_at_matches_dense_insert(rng):
    dim = 5000
    cs = make_csvec(rng, dim=dim, rows=5, cols=512)
    idx = jax.random.choice(rng, dim, (37,), replace=False)
    vals = jax.random.normal(jax.random.fold_in(rng, 1), (37,))
    dense = jnp.zeros(dim).at[idx].set(vals)
    np.testing.assert_allclose(
        np.asarray(insert_at(cs, idx, vals).table),
        np.asarray(insert(cs, dense).table), atol=1e-5, rtol=1e-5)


# -- p2 second-round exact-value exchange -------------------------------------


def test_p2_exchange_reduces_estimation_error(rng):
    """With cs_p2 > 0 the transmitted values are the TRUE residual
    values at the nominated candidates — estimation error on the sent
    coordinates collapses to ~0, vs the sketch-noise floor at p2=0."""
    from jax.flatten_util import ravel_pytree

    dim = 20000
    g = {"w": jax.random.normal(rng, (dim,)) ** 3}
    flat, _ = ravel_pytree(g)
    err_by_p2 = {}
    for p2 in (0, 4):
        cfg = CompressionConfig(mode="countsketch", cs_rows=5,
                                cs_cols=1024, cs_k=64, cs_momentum=0.0,
                                cs_p2=p2, cs_chunk=4096)
        comp, _, stats = compress_grads_countsketch(
            g, init_countsketch_state(g), cfg)
        c, _ = ravel_pytree(comp)
        sent = np.asarray(c) != 0
        assert sent.sum() <= cfg.cs_k
        err_by_p2[p2] = float(jnp.linalg.norm(c[sent] - flat[sent]))
        if p2 > 0:
            # second round adds p2*k f32 values to the wire
            assert stats["wire_bytes"] == 5 * 1024 * 4 + p2 * 64 * 4
    assert err_by_p2[4] < 1e-4 < err_by_p2[0]


def test_p2_mass_conservation(rng):
    """Residual subtraction stays exact with the p2 exchange on."""
    cfg = CompressionConfig(mode="countsketch", cs_rows=5, cs_cols=512,
                            cs_k=64, cs_momentum=0.9, cs_p2=2)
    grads = _toy_grads(rng)
    err = init_countsketch_state(grads)
    comp, new_err, _ = compress_grads_countsketch(grads, err, cfg)

    from jax.flatten_util import ravel_pytree
    flat_g, _ = ravel_pytree(grads)
    flat_c, _ = ravel_pytree(comp)
    u = cfg.cs_momentum * err["u"] + flat_g
    v_pre = err["v"] + u
    np.testing.assert_allclose(
        np.asarray(new_err["v"] + flat_c), np.asarray(v_pre),
        atol=1e-6, rtol=1e-6)
    sent = np.asarray(flat_c) != 0
    assert np.all(np.asarray(new_err["u"])[sent] == 0.0)


# -- geometry resolution / fail-fast validation -------------------------------


def test_cs_cols_autosizes_from_dim():
    cfg = CompressionConfig(mode="countsketch", cs_rows=5,
                            cs_target_ratio=0.05)
    assert cfg.cs_cols is None
    r = resolve_countsketch(cfg, 1_000_000, strict=True)
    assert r.cs_cols == 8192                 # prev pow2 of 50000/5
    assert r.cs_rows * r.cs_cols * 4 <= 0.05 * 1_000_000 * 4
    # idempotent
    assert resolve_countsketch(r, 1_000_000, strict=True) == r


def test_cs_geometry_fails_fast():
    cfg = CompressionConfig(mode="countsketch", cs_rows=5)
    with pytest.raises(ValueError, match="auto-size"):
        resolve_countsketch(cfg, 5000)       # too small for the budget
    big = CompressionConfig(mode="countsketch", cs_rows=5, cs_cols=2048)
    with pytest.raises(ValueError, match="not smaller"):
        resolve_countsketch(big, 5000, strict=True)
    with pytest.raises(ValueError, match="cs_k"):
        resolve_countsketch(
            CompressionConfig(mode="countsketch", cs_rows=2, cs_cols=128,
                              cs_k=5000), 2048, strict=True)
    with pytest.raises(ValueError, match="power of two"):
        CompressionConfig(mode="countsketch", cs_cols=100)
    with pytest.raises(ValueError, match="cs_rows"):
        CompressionConfig(mode="countsketch", cs_rows=0)
    with pytest.raises(ValueError, match="cs_p2"):
        CompressionConfig(mode="countsketch", cs_p2=-1)


def test_run_config_autosizes_at_state_construction():
    """finalize_run resolves cs_cols against the model's flat dim before
    any kernel sees the geometry."""
    from repro.configs import get_arch, reduced
    from repro.train.state import RunConfig, finalize_run
    from repro.models.transformer import SketchSettings

    cfg = reduced(get_arch("tinyllama-1.1b"))
    run = RunConfig(seq_len=16, global_batch=4,
                    sketch=SketchSettings(enabled=False),
                    compression=CompressionConfig(mode="countsketch",
                                                  cs_k=256))
    fin = finalize_run(cfg, run)
    cols = fin.compression.cs_cols
    assert cols is not None and cols & (cols - 1) == 0
    from repro.models.transformer import init_params
    d = flat_dim(init_params(jax.random.PRNGKey(0), cfg))
    assert fin.compression.cs_rows * cols * 4 <= \
        fin.compression.cs_target_ratio * d * 4
    # finalize is idempotent — a resolved run passes through unchanged
    assert finalize_run(cfg, fin) == fin


# -- error feedback -----------------------------------------------------------


def _toy_grads(key, shapes=((64, 32), (512,), (16, 16, 4))):
    return {f"p{i}": jax.random.normal(jax.random.fold_in(key, i), s)
            for i, s in enumerate(shapes)}


def test_error_feedback_mass_conservation(rng):
    """Residual-subtraction error feedback: v_new + update == v_old + u
    exactly (unsent mass — including sketch estimation error — stays
    local and re-injects next step)."""
    cfg = CompressionConfig(mode="countsketch", cs_rows=5, cs_cols=512,
                            cs_k=64, cs_momentum=0.9)
    grads = _toy_grads(rng)
    err = init_countsketch_state(grads)
    comp, new_err, _ = compress_grads_countsketch(grads, err, cfg)

    from jax.flatten_util import ravel_pytree
    flat_g, _ = ravel_pytree(grads)
    flat_c, _ = ravel_pytree(comp)
    u = cfg.cs_momentum * err["u"] + flat_g        # step's accumulator
    v_pre = err["v"] + u
    np.testing.assert_allclose(
        np.asarray(new_err["v"] + flat_c), np.asarray(v_pre),
        atol=1e-6, rtol=1e-6)
    # momentum zeroed exactly on transmitted coordinates
    sent = np.asarray(flat_c) != 0
    assert sent.sum() <= cfg.cs_k
    assert np.all(np.asarray(new_err["u"])[sent] == 0.0)
    np.testing.assert_array_equal(np.asarray(new_err["u"])[~sent],
                                  np.asarray(u)[~sent])


def test_error_feedback_converges_on_fixed_gradient(rng):
    """Feeding the same sparse gradient repeatedly, the transmitted mass
    catches up with the true gradient (error feedback is unbiased over
    time): cumulative update approaches step * g on heavy coords."""
    cfg = CompressionConfig(mode="countsketch", cs_rows=5, cs_cols=1024,
                            cs_k=32, cs_momentum=0.0)
    g = {"w": jnp.zeros(5000).at[jnp.arange(0, 5000, 250)].set(5.0)}
    err = init_countsketch_state(g)
    total = jnp.zeros(5000)
    steps = 10
    for _ in range(steps):
        comp, err, _ = compress_grads_countsketch(g, err, cfg)
        total = total + comp["w"]
    heavy = np.arange(0, 5000, 250)
    np.testing.assert_allclose(np.asarray(total)[heavy],
                               steps * 5.0, rtol=0.1)


# -- wire accounting + train-step wiring -------------------------------------


def test_compressed_bytes_countsketch_independent_of_dim():
    cfg = CompressionConfig(mode="countsketch", cs_rows=5, cs_cols=2048)
    assert compressed_bytes(10 ** 6, cfg) == 5 * 2048 * 4
    assert compressed_bytes(10 ** 9, cfg) == 5 * 2048 * 4


def test_table_bytes_matches_config(rng):
    cs = make_csvec(rng, dim=999, rows=3, cols=128)
    assert table_bytes(cs) == 3 * 128 * 4


def test_countsketch_train_step_runs_and_descends():
    from repro.configs import get_arch, reduced
    from repro.data.synthetic import lm_batch
    from repro.models.transformer import SketchSettings
    from repro.train.state import RunConfig, init_train_state
    from repro.train.step import make_train_step

    cfg = reduced(get_arch("tinyllama-1.1b"))
    ccfg = CompressionConfig(mode="countsketch", cs_rows=5,
                             cs_cols=2048, cs_k=512)
    run = RunConfig(seq_len=16, global_batch=4, compression=ccfg,
                    sketch=SketchSettings(enabled=False))
    key = jax.random.PRNGKey(0)
    state = init_train_state(key, cfg, run)
    assert set(state.opt["err"]) == {"u", "v"}
    assert state.opt["err"]["u"].shape == (flat_dim(state.params),)
    step = jax.jit(make_train_step(cfg, run))
    tokens, labels = lm_batch(key, 4, 16, cfg.vocab_size)
    losses = []
    for i in range(8):
        state, m = step(state, {"tokens": tokens, "labels": labels})
        losses.append(float(m["loss"]))
        assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0]  # memorizing one batch must descend


def test_countsketch_psum_path_under_shard_map(rng):
    """The dp_axis_name path: a 1-device shard_map exercises the psum
    merge wiring (W=1 — psum identity) and must match the axis-free
    path exactly."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    cfg = CompressionConfig(mode="countsketch", cs_rows=3, cs_cols=256,
                            cs_k=32)
    grads = _toy_grads(rng, shapes=((128,), (32, 8)))
    err = init_countsketch_state(grads)
    want, want_err, _ = compress_grads_countsketch(grads, err, cfg)

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    fn = shard_map(
        lambda g, e: compress_grads_countsketch(
            g, e, cfg, axis_name="data")[:2],
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False)
    got, got_err = fn(grads, err)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)
    for a, b in zip(jax.tree.leaves(got_err), jax.tree.leaves(want_err)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)


@pytest.mark.slow
def test_countsketch_psum_matches_single_worker_on_4_devices():
    """Real W=4 psum merge on fake CPU devices (subprocess, same pattern
    as test_distributed): compressing per-worker grad shards under
    shard_map must equal compressing the worker-mean gradient directly
    — up to sketch-table float summation order."""
    import os
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.optim.compression import CompressionConfig
        from repro.optim.sketched_sgd import (
            compress_grads_countsketch, init_countsketch_state)

        W, dim = 4, 4096
        cfg = CompressionConfig(mode="countsketch", cs_rows=5,
                                cs_cols=512, cs_k=128)
        key = jax.random.PRNGKey(0)
        worker_g = jax.random.normal(key, (W, dim))   # (W, D) shards
        err = init_countsketch_state({"w": worker_g[0]})

        mesh = Mesh(np.array(jax.devices()), ("data",))
        fn = shard_map(
            lambda g, e: compress_grads_countsketch(
                {"w": g.reshape(dim)}, e, cfg, axis_name="data")[0]["w"],
            mesh=mesh, in_specs=(P("data"), P()), out_specs=P(),
            check_vma=False)
        got = fn(worker_g, err)

        want = compress_grads_countsketch(
            {"w": worker_g.mean(0)}, err, cfg)[0]["w"]
        # psum sums tables; the single-worker path sketches the mean —
        # worker-count normalization must line up
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK" in out.stdout


@pytest.mark.slow
def test_w4_shard_map_end_to_end_step_with_p2():
    """Real W=4 DP train step under shard_map (fake CPU devices in a
    subprocess): replicated state descends and matches the W=1 step
    bit-close; compress-level checks assert exact per-worker mass
    conservation and that the p2 exchange reduces estimation error on
    the transmitted coordinates vs p2=0."""
    import os
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.flatten_util import ravel_pytree
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_arch, reduced
        from repro.data.synthetic import lm_batch
        from repro.models.transformer import SketchSettings
        from repro.optim.compression import CompressionConfig
        from repro.optim.sketched_sgd import (
            compress_grads_countsketch, init_countsketch_state)
        from repro.train.state import RunConfig, init_train_state
        from repro.train.step import make_dp_train_step, make_train_step

        mesh = Mesh(np.array(jax.devices()), ("data",))
        W, dim = 4, 8192
        key = jax.random.PRNGKey(0)

        # -- compress-level: mass conservation + p2 error, real psum --
        worker_g = jax.random.normal(key, (W, dim)) ** 3
        err = init_countsketch_state({"w": worker_g[0]})
        errs = {}
        for p2 in (0, 4):
            cfg = CompressionConfig(mode="countsketch", cs_rows=5,
                                    cs_cols=512, cs_k=64,
                                    cs_momentum=0.0, cs_p2=p2,
                                    cs_chunk=2048)
            def compress(g, e, cfg=cfg):
                comp, ne, _ = compress_grads_countsketch(
                    {"w": g.reshape(dim)}, e, cfg, axis_name="data")
                # lead with a singleton axis so out_specs P("data")
                # STACKS the per-worker err states into (W, dim)
                return comp, jax.tree.map(
                    lambda x: x.reshape(1, -1), ne)
            fn = shard_map(
                compress, mesh=mesh, in_specs=(P("data"), P()),
                out_specs=(P(), P("data")), check_vma=False)
            comp, new_err = fn(worker_g, err)
            c = comp["w"]
            # per-worker exact mass conservation: v_new + update == v_pre
            for w in range(W):
                v_pre = err["v"] + worker_g[w]
                np.testing.assert_allclose(
                    np.asarray(new_err["v"][w] + c), np.asarray(v_pre),
                    atol=1e-5, rtol=1e-5)
            sent = np.asarray(c) != 0
            true_mean = worker_g.mean(0)
            errs[p2] = float(jnp.linalg.norm(
                c[sent] - true_mean[sent]))
        assert errs[4] < errs[0], errs
        assert errs[4] < 1e-3, errs

        # -- end-to-end: W=4 DP step descends and tracks W=1 ----------
        cfg_a = reduced(get_arch("tinyllama-1.1b"))
        ccfg = CompressionConfig(mode="countsketch", cs_rows=5,
                                 cs_k=512, cs_p2=2)
        mk = lambda ax: RunConfig(
            seq_len=16, global_batch=8, compression=ccfg,
            sketch=SketchSettings(enabled=False), dp_axis_name=ax,
            warmup_steps=2, total_steps=50)
        tok, lab = lm_batch(key, 8, 16, cfg_a.vocab_size)
        batch = {"tokens": tok, "labels": lab}

        state = init_train_state(key, cfg_a, mk("data"))
        state = jax.device_put(state, NamedSharding(mesh, P()))
        dp_step = jax.jit(make_dp_train_step(cfg_a, mk("data"), mesh))
        s1 = init_train_state(key, cfg_a, mk(None))
        ref_step = jax.jit(make_train_step(cfg_a, mk(None)))
        dp_l, ref_l = [], []
        for i in range(6):
            state, m = dp_step(state, batch)
            dp_l.append(float(m["loss"]))
            s1, m1 = ref_step(s1, batch)
            ref_l.append(float(m1["loss"]))
        assert all(np.isfinite(dp_l))
        assert dp_l[-1] < dp_l[0]
        np.testing.assert_allclose(dp_l, ref_l, atol=1e-3, rtol=1e-4)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK" in out.stdout


def test_zero_table_and_hash_params_deterministic(rng):
    cs1 = make_csvec(rng, dim=500, rows=4, cols=128)
    cs2 = make_csvec(rng, dim=500, rows=4, cols=128)
    np.testing.assert_array_equal(np.asarray(cs1.params),
                                  np.asarray(cs2.params))
    v = jax.random.normal(rng, (500,))
    filled = insert(cs1, v)
    assert float(jnp.abs(zero_table(filled).table).max()) == 0.0
    # a is odd in both hash rows (2-universality precondition)
    assert np.all(np.asarray(cs1.params)[0] % 2 == 1)
    assert np.all(np.asarray(cs1.params)[2] % 2 == 1)


def test_query_all_shape_and_cols_validation(rng):
    with pytest.raises(ValueError):
        make_csvec(rng, dim=10, rows=2, cols=100)   # not a power of two
    cs = make_csvec(rng, dim=300, rows=3, cols=128)
    assert query_all(insert(cs, jnp.ones(300))).shape == (300,)


# ---------------------------------------------------------------------------
# ISSUE 4: int8 wire + flat-segment wire format
# ---------------------------------------------------------------------------


def test_quant_kernel_matches_reference(rng):
    """Pallas csvec_quant vs the jnp reference: q/scale/dhat bit-exact;
    resid within one ulp of the row amax (XLA may FMA-contract the
    final multiply-subtract)."""
    from repro.kernels.csvec_quant import csvec_quant, csvec_quant_ref

    for seed, shape, mult in [(0, (5, 256), 10.0), (1, (3, 128), 1e-4),
                              (2, (7, 512), 1e6), (3, (1, 128), 0.0)]:
        t = jax.random.normal(jax.random.PRNGKey(seed), shape) * mult
        t = t.at[0].set(0.0) if seed == 2 else t   # an all-zero row
        got = csvec_quant(t)
        want = csvec_quant_ref(t)
        for name, a, b in zip(("q", "scale", "dhat"), got[:3],
                              want[:3]):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \
                (seed, name)
        ulp = np.spacing(np.float32(np.abs(np.asarray(t)).max()))
        d = np.abs(np.asarray(got[3]) - np.asarray(want[3])).max()
        assert d <= max(float(ulp), 0.0), (seed, d)


def test_compressed_bytes_int8_accounting():
    """int8 wire = 1 byte/counter + r f32 scales (+ p2 round)."""
    base = dict(mode="countsketch", cs_rows=5, cs_cols=1024, cs_k=64)
    f32 = CompressionConfig(**base)
    i8 = CompressionConfig(**base, wire_dtype="int8")
    assert compressed_bytes(10 ** 6, f32) == 5 * 1024 * 4
    assert compressed_bytes(10 ** 6, i8) == 5 * 1024 + 5 * 4
    i8p2 = CompressionConfig(**base, wire_dtype="int8", cs_p2=2)
    assert compressed_bytes(10 ** 6, i8p2) == \
        5 * 1024 + 5 * 4 + 2 * 64 * 4
    with pytest.raises(ValueError):
        CompressionConfig(**base, wire_dtype="fp16")


def test_int8_error_feedback_converges_on_fixed_gradient(rng):
    """The int8 twin of the fp32 convergence test above: feeding the
    same sparse heavy gradient repeatedly through the int8-wire
    compressor, the cumulative transmitted mass still catches up with
    steps * g — the error-feedback buffer absorbs the quantization
    residual on top of the sketch estimation error — and the exact
    decomposition sent + v == steps * g (mass conservation across the
    whole run) holds to fp accumulation tolerance."""
    cfg = CompressionConfig(mode="countsketch", cs_rows=5, cs_cols=1024,
                            cs_k=32, cs_momentum=0.0, wire_dtype="int8")
    g = {"w": jnp.zeros(5000).at[jnp.arange(0, 5000, 250)].set(5.0)}
    err = init_countsketch_state(g)
    sent = jnp.zeros(5000)
    steps = 10
    for _ in range(steps):
        comp, err, _ = compress_grads_countsketch(g, err, cfg)
        sent = sent + comp["w"]
    heavy = np.arange(0, 5000, 250)
    np.testing.assert_allclose(np.asarray(sent)[heavy], steps * 5.0,
                               rtol=0.1)
    np.testing.assert_allclose(np.asarray(sent + err["v"]),
                               np.asarray(steps * g["w"]), atol=1e-3)


# -- property tests (hypothesis-fuzzed in CI, seeded fallback locally) ------


def _check_quant_mass_exact(seed: int, rows: int, cols: int,
                            scale_exp: int):
    """quantize -> dequantize + residual reproduces the table: bitwise
    with the reference decomposition, and the row SUM is preserved to
    fp32 ulp resolution."""
    from repro.countsketch.csvec import (
        dequantize_table, quantize_residual, quantize_table,
    )

    t = jax.random.normal(jax.random.PRNGKey(seed), (rows, cols)) \
        * (10.0 ** scale_exp)
    q, scale = quantize_table(t)
    dhat = dequantize_table(q, scale)
    resid = quantize_residual(t, q, scale)
    assert np.array_equal(np.asarray(dhat + resid), np.asarray(t))
    row_amax = np.abs(np.asarray(t)).max(axis=1)
    sum_err = np.abs(np.asarray((dhat + resid).sum(axis=1) -
                                t.sum(axis=1)))
    assert np.all(sum_err <= cols * np.spacing(
        row_amax.astype(np.float32)))


def _check_quantized_merge_linearity(seed: int, workers: int,
                                     rows: int, cols: int):
    """Merging W quantized tables (sum of dequantized grids — exactly
    what an int8 all-gather + local dequant-sum computes) deviates from
    the exact f32 merge by at most the stacked rounding bound
    sum_w scale_w / 2 per entry — the amount the per-worker error
    feedback retains."""
    from repro.countsketch.csvec import dequantize_table, quantize_table

    key = jax.random.PRNGKey(seed)
    tables = jax.random.normal(key, (workers, rows, cols)) * \
        jnp.exp(jax.random.normal(jax.random.fold_in(key, 1),
                                  (workers, 1, 1)))
    merged_q = jnp.zeros((rows, cols))
    bound = jnp.zeros((rows, 1))
    for w in range(workers):
        q, scale = quantize_table(tables[w])
        merged_q = merged_q + dequantize_table(q, scale)
        bound = bound + scale[:, None] / 2.0
    exact = tables.sum(axis=0)
    slack = 1.0 + 1e-5     # fp accumulation slop on the bound itself
    assert np.all(np.abs(np.asarray(merged_q - exact)) <=
                  np.asarray(bound) * slack + 1e-12)


def _check_pack_roundtrip(seed: int, shapes):
    """pack/unpack over ragged node shapes is a bitwise bijection in
    both directions (unpack∘pack == id on leaves; pack∘unpack == id on
    the flat buffer)."""
    from repro.sketches.wire import (
        pack_segments, segment_spec, unpack_segments,
    )

    key = jax.random.PRNGKey(seed)
    tree = {f"n{i}": jax.random.normal(jax.random.fold_in(key, i), s)
            for i, s in enumerate(shapes)}
    spec = segment_spec(tree)
    flat = pack_segments(tree)
    assert flat.shape == (spec.total,)
    back = unpack_segments(spec, flat)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    flat2 = pack_segments(back)
    assert np.array_equal(np.asarray(flat), np.asarray(flat2))


@pytest.mark.parametrize("seed,rows,cols,scale_exp", [
    (0, 5, 256, 0), (1, 3, 128, -6), (2, 7, 512, 6), (3, 1, 128, 2),
])
def test_quant_mass_exact_seeded(seed, rows, cols, scale_exp):
    _check_quant_mass_exact(seed, rows, cols, scale_exp)


@pytest.mark.parametrize("seed,workers,rows,cols", [
    (0, 4, 5, 256), (1, 2, 3, 128), (2, 8, 5, 512),
])
def test_quantized_merge_linearity_seeded(seed, workers, rows, cols):
    _check_quantized_merge_linearity(seed, workers, rows, cols)


@pytest.mark.parametrize("seed,shapes", [
    (0, [(3, 24, 9), (24, 9), (5, 7), (19,)]),          # mixed ranks
    (1, [(9, 16), (48, 9), (19, 19)]),                  # corange-ish
    (2, [(1,)]),
    (3, [(2, 3), (0, 5), (4,)]),                        # empty leaf
])
def test_pack_roundtrip_seeded(seed, shapes):
    _check_pack_roundtrip(seed, shapes)


try:
    from hypothesis import given, settings, strategies as st
    _HYP_SETTINGS = dict(max_examples=25, deadline=None)

    @given(st.integers(0, 2 ** 31 - 1), st.integers(1, 8),
           st.sampled_from([128, 256, 512]), st.integers(-6, 6))
    @settings(**_HYP_SETTINGS)
    def test_quant_mass_exact_property(seed, rows, cols, scale_exp):
        _check_quant_mass_exact(seed, rows, cols, scale_exp)

    @given(st.integers(0, 2 ** 31 - 1), st.integers(1, 8),
           st.integers(1, 6), st.sampled_from([128, 256]))
    @settings(**_HYP_SETTINGS)
    def test_quantized_merge_linearity_property(seed, workers, rows,
                                                cols):
        _check_quantized_merge_linearity(seed, workers, rows, cols)

    @given(st.integers(0, 2 ** 31 - 1),
           st.lists(st.lists(st.integers(0, 12), min_size=1,
                             max_size=3),
                    min_size=1, max_size=6))
    @settings(**_HYP_SETTINGS)
    def test_pack_roundtrip_property(seed, shapes):
        _check_pack_roundtrip(seed, [tuple(s) for s in shapes])
except ImportError:     # hypothesis is a dev-only dependency
    pass
