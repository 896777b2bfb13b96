"""Attention (chunked/decode/windowed) + MoE dispatch correctness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch, reduced
from repro.kernels.ref import flash_attention_ref
from repro.models import attention
from repro.models.attention import chunked_causal_attention
from repro.models.moe import moe_apply_ref, moe_dense_ref, moe_init
from repro.models.transformer import SketchSettings
from repro.parallel.collectives import (
    merge_partial_attn_pair, partial_attn_stats,
)
from repro.parallel.sharding import ShardingRules, use_rules
from repro.telemetry import attention_paths
from repro.train.state import RunConfig, init_train_state
from repro.train.step import make_dp_train_step


@pytest.mark.parametrize("window", [None, 16])
def test_chunked_attention_matches_ref(rng, window):
    B, S, KV, G, D = 2, 64, 2, 2, 16
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, S, KV, G, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    got = chunked_causal_attention(q, k, v, window=window, chunk=16)
    # ref expects (B, H, S, D)
    qh = q.reshape(B, S, KV * G, D).transpose(0, 2, 1, 3)
    want = flash_attention_ref(
        qh, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True, window=window)
    want = want.transpose(0, 2, 1, 3).reshape(B, S, KV, G, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def _kernel_matches_scan(rng, kv, g, seq, window):
    """The kernel path (interpreted) against the XLA scan, both in bf16:
    output and the q, k, v gradients of one vjp."""
    B, D = 1, 128
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (B, seq, kv, g, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, seq, kv, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, seq, kv, D), jnp.bfloat16)
    do = jax.random.normal(ks[3], (B, seq, kv, g, D), jnp.bfloat16)

    def kernel(q, k, v):
        qf = (q * D ** -0.5).astype(q.dtype)
        return attention.splash_causal_attention(qf, k, v, window=window)

    def scan(q, k, v):
        return chunked_causal_attention(q, k, v, window=window)

    got_o, got_vjp = jax.vjp(kernel, q, k, v)
    want_o, want_vjp = jax.vjp(scan, q, k, v)
    assert got_o.dtype == jnp.bfloat16 and got_o.shape == q.shape
    for got, want in zip((got_o, *got_vjp(do)), (want_o, *want_vjp(do))):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("kv,g", [(1, 4), (2, 2)], ids=["mqa", "gqa"])
def test_splash_kernel_matches_scan(rng, kv, g, window):
    """One block a row: the kernel's block rule at S 256."""
    assert attention.kernel_block(256, 128) == 256
    _kernel_matches_scan(rng, kv, g, 256, window)


@pytest.mark.parametrize("window", [None, 64, 200])
@pytest.mark.parametrize("kv,g", [(1, 4), (2, 2)], ids=["mqa", "gqa"])
def test_splash_kernel_multi_block_matches_scan(monkeypatch, rng, kv, g,
                                                window):
    """Four 128 blocks a row at S 512: the causal block table skips the
    blocks above the diagonal, a window of 64 or 200 spans block edges
    and skips the blocks wholly outside it."""
    monkeypatch.setattr(attention, "kernel_block", lambda seq, d: 128)
    _kernel_matches_scan(rng, kv, g, 512, window)


@pytest.mark.parametrize("platform,mesh,seq,path", [
    ("cpu", None, 256, "scan"),
    ("tpu", (2, 2), 256, "scan"),      # a mesh of more than one device
    ("tpu", (1, 1), 256, "kernel"),
    ("tpu", None, 256, "kernel"),
    ("tpu", None, 192, "scan"),        # not whole 128-lane blocks
])
def test_dispatch_picks_kernel_or_scan(monkeypatch, platform, mesh, seq,
                                       path):
    monkeypatch.setattr(attention, "_platform", lambda: platform)
    q = jax.ShapeDtypeStruct((1, seq, 2, 2, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, seq, 2, 128), jnp.bfloat16)
    rules = None if mesh is None else ShardingRules(
        mesh=AbstractMesh(mesh, ("data", "model")), dp_axes=("data",))
    before = attention_paths()
    with use_rules(rules):
        jax.eval_shape(lambda q, k, v: chunked_causal_attention(
            q, k, v, window=None), q, kv, kv)
    after = attention_paths()
    assert {p: after[p] - before[p] for p in after} == {
        p: int(p == path) for p in after}


@pytest.mark.parametrize("devices,path", [(4, "scan"), (1, "kernel")])
def test_dispatch_inside_shard_map(monkeypatch, devices, path):
    """A shard_map's mesh counts as a mesh, though it sets no rules."""
    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    mesh = AbstractMesh((devices,), ("data",))
    q = jax.ShapeDtypeStruct((devices, 256, 2, 2, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((devices, 256, 2, 128), jnp.bfloat16)
    attend = shard_map(
        lambda q, k, v: chunked_causal_attention(q, k, v, window=None),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False)
    before = attention_paths()
    jax.eval_shape(attend, q, kv, kv)
    after = attention_paths()
    assert {p: after[p] - before[p] for p in after} == {
        p: int(p == path) for p in after}


def test_dp_train_step_keeps_the_scan(monkeypatch):
    """The data-parallel step (a shard_map over four workers, no sharding
    rules) traces the scan even where the platform is a TPU."""
    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    cfg = reduced(get_arch("tinyllama-1.1b"))
    run = RunConfig(global_batch=4, seq_len=256, total_steps=4,
                    warmup_steps=1, dp_axis_name="data", dp_workers=4,
                    sketch=SketchSettings(enabled=True, k_max=9))
    state = jax.eval_shape(lambda key: init_train_state(key, cfg, run),
                           jax.random.PRNGKey(0))
    batch = {n: jax.ShapeDtypeStruct((4, 256), jnp.int32)
             for n in ("tokens", "labels")}
    step = make_dp_train_step(cfg, run, AbstractMesh((4,), ("data",)))
    before = attention_paths()
    jax.eval_shape(step, state, batch)
    after = attention_paths()
    assert after["kernel"] == before["kernel"]
    assert after["scan"] > before["scan"]


def test_ring_cache_decode_matches_full_context(rng):
    """Windowed ring cache decode == full attention restricted to the
    window (SWA archs at long context)."""
    from repro.models.transformer import forward, init_params
    import dataclasses as dc
    cfg = dc.replace(reduced(get_arch("mixtral-8x22b")), window_size=8,
                     capacity_factor=8.0)
    params = init_params(rng, cfg)
    B, S = 2, 24
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    ref = forward(params, tokens, cfg=cfg, mode="train")["logits"]
    pf = forward(params, tokens[:, :S - 1], cfg=cfg, mode="prefill",
                 seq_len_ctx=S)
    # ring: capacity = window 8 < S 24
    assert pf["cache"]["groups"][0]["k"].shape[3] == 8
    dec = forward(params, tokens[:, S - 1:], cfg=cfg, mode="decode",
                  positions=jnp.full((B,), S - 1, jnp.int32),
                  cache=pf["cache"], seq_len_ctx=S)
    np.testing.assert_allclose(
        np.asarray(dec["logits"][:, 0]), np.asarray(ref[:, S - 1]),
        atol=2e-3, rtol=2e-3)


def test_merge_partial_attn_equals_full_softmax(rng):
    """Flash-decoding LSE merge across cache shards == full attention."""
    B, H, C, D, shards = 2, 4, 32, 16, 4
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, H, 1, D))
    k = jax.random.normal(ks[1], (B, H, C, D))
    v = jax.random.normal(ks[2], (B, H, C, D))
    mask = jnp.ones((B, C), bool)
    # full softmax reference
    s = jnp.einsum("bhqd,bhcd->bhqc", q, k) * D ** -0.5
    p = jax.nn.softmax(s, -1)
    want = jnp.einsum("bhqc,bhcd->bhqd", p, v)
    # sharded partials + merge
    parts = []
    for i in range(shards):
        sl = slice(i * C // shards, (i + 1) * C // shards)
        parts.append(partial_attn_stats(q, k[:, :, sl], v[:, :, sl],
                                        mask[:, sl]))
    got = merge_partial_attn_pair(parts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_moe_matches_dense_oracle_without_drops(rng):
    cfg = dataclasses.replace(reduced(get_arch("qwen3-moe-30b-a3b")),
                              capacity_factor=8.0)
    p = moe_init(rng, cfg, jnp.float32)
    x = jax.random.normal(rng, (64, cfg.d_model))
    y1, aux = moe_apply_ref(p, x, cfg)
    y2 = moe_dense_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-5, rtol=1e-5)
    assert float(aux) > 0.9          # ~1 for near-uniform routing


def test_moe_capacity_drops_reduce_output_mass(rng):
    cfg = dataclasses.replace(reduced(get_arch("qwen3-moe-30b-a3b")),
                              capacity_factor=0.25)
    p = moe_init(rng, cfg, jnp.float32)
    x = jax.random.normal(rng, (64, cfg.d_model))
    y_drop, _ = moe_apply_ref(p, x, cfg)
    cfg_full = dataclasses.replace(cfg, capacity_factor=8.0)
    y_full, _ = moe_apply_ref(p, x, cfg_full)
    assert float(jnp.linalg.norm(y_drop)) < float(jnp.linalg.norm(y_full))
