"""Flash-attention Pallas kernel (causal / sliding-window, GQA).

Online-softmax tiling: grid (B*Hq, q_blocks, kv_blocks) with the KV axis
innermost so the (q_blk, D) accumulator, running max and running sum stay
VMEM-resident across the KV sweep. Fully-masked KV blocks (beyond the
causal frontier or outside the sliding window) are skipped with pl.when —
on TPU this prunes ~half the blocks for causal and all but window/S for
SWA. Q/K/V tiles are MXU-aligned when D is a multiple of 128 (all full
configs); CPU tests run small shapes in interpret mode against
kernels.ref.flash_attention_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int | None,
            q_blk: int, kv_blk: int, n_kv: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * q_blk
    k_start = kj * kv_blk
    # block-level skip: causal (k block entirely after q block) and
    # window (k block entirely before the window of the oldest q row)
    live = True
    if causal:
        live = k_start <= q_start + q_blk - 1
    if window is not None:
        live = jnp.logical_and(
            live, k_start + kv_blk - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (q_blk, D)
        k = k_ref[0].astype(jnp.float32)                  # (kv_blk, D)
        s = jax.lax.dot(q, k.T, preferred_element_type=jnp.float32)
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (q_blk, kv_blk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (q_blk, kv_blk), 1)
        mask = jnp.ones((q_blk, kv_blk), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
        v = v_ref[0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kj == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_blk", "kv_blk", "interpret"),
)
def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None,
                    q_blk: int = 256, kv_blk: int = 256,
                    interpret: bool | None = None):
    """q (B, Hq, S, D); k/v (B, Hkv, S, D) -> (B, Hq, S, D)."""
    if interpret is None:
        from repro.kernels.ops import interpret_mode
        interpret = interpret_mode()
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    q_blk = min(q_blk, S)
    kv_blk = min(kv_blk, S)
    assert S % q_blk == 0 and S % kv_blk == 0
    qf = q.reshape(B * Hq, S, D)
    kf = k.reshape(B * Hkv, S, D)
    vf = v.reshape(B * Hkv, S, D)
    grid = (B * Hq, S // q_blk, S // kv_blk)
    from jax.experimental.pallas import tpu as pltpu

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=D ** -0.5, causal=causal, window=window,
            q_blk=q_blk, kv_blk=kv_blk, n_kv=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_blk, D), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, kv_blk, D),
                         lambda bh, qi, kj, G=G: (bh // G, kj, 0)),
            pl.BlockSpec((1, kv_blk, D),
                         lambda bh, qi, kj, G=G: (bh // G, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_blk, D), lambda bh, qi, kj: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk,), jnp.float32),
            pltpu.VMEM((q_blk,), jnp.float32),
            pltpu.VMEM((q_blk, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hq, S, D)
