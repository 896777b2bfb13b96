"""Chunkwise mLSTM Pallas kernel (xLSTM hot spot, DESIGN.md §2).

One grid step = one (batch*head, chunk) cell; the chunk axis is innermost
so the stabilized matrix-memory state (C (Dk, Dv), n (Dk,), m ()) lives in
VMEM scratch across the sequence sweep — the recurrence never round-trips
HBM. Within a chunk the math is the masked-decay attention form (matmul-
heavy, MXU-friendly); across chunks the exponential-gating stabilizer is
carried exactly as in models/ssm._mlstm_chunk_scan, which is the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, h_ref,
            cs_ref, ns_ref, ms_ref,
            C_scr, n_scr, m_scr, *, W: int, Dk: int, Dv: int, n_c: int):
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _init():
        C_scr[...] = jnp.zeros_like(C_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.zeros_like(m_scr)

    q = q_ref[0].astype(jnp.float32) * (Dk ** -0.5)   # (W, Dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)                  # (W, Dv)
    li = li_ref[0].astype(jnp.float32)                # (W,)
    lf = lf_ref[0].astype(jnp.float32)
    C = C_scr[...]
    n = n_scr[...]                                    # (1, Dk)
    m = m_scr[0, 0]

    F = jnp.cumsum(lf)                                # (W,)
    Ftot = F[-1]
    wlog = F[:, None] - F[None, :] + li[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
    wlog = jnp.where(tri, wlog, -jnp.inf)
    b_inter = F + m
    mj = jnp.maximum(wlog.max(axis=-1), b_inter)
    D = jnp.exp(wlog - mj[:, None])
    inter = jnp.exp(b_inter - mj)
    s = jax.lax.dot(q, k.T, preferred_element_type=jnp.float32) * D
    num = jax.lax.dot(s, v, preferred_element_type=jnp.float32) + \
        inter[:, None] * jax.lax.dot(q, C,
                                     preferred_element_type=jnp.float32)
    den = s.sum(axis=-1) + inter * (q @ n[0])
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-mj))[:, None]
    h_ref[0] = h.astype(h_ref.dtype)

    m_kv = (Ftot - F + li).max()
    m_new = jnp.maximum(Ftot + m, m_kv)
    wkv = jnp.exp(Ftot - F + li - m_new)              # (W,)
    decay = jnp.exp(Ftot + m - m_new)
    C_scr[...] = decay * C + jax.lax.dot(
        (k * wkv[:, None]).T, v, preferred_element_type=jnp.float32)
    n_scr[...] = decay * n + (wkv[None, :] @ k)
    m_scr[0, 0] = m_new

    @pl.when(cj == n_c - 1)
    def _final():
        cs_ref[0] = C_scr[...]
        ns_ref[0] = n_scr[...]
        ms_ref[0] = m_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunk(q, k, v, li, lf, *, chunk: int = 256,
                interpret: bool | None = None):
    """q,k (B,H,S,Dk); v (B,H,S,Dv); li,lf (B,H,S) log gates.
    Returns h (B,H,S,Dv), (C (B,H,Dk,Dv), n (B,H,Dk), m (B,H))."""
    if interpret is None:
        from repro.kernels.ops import interpret_mode
        interpret = interpret_mode()
    B, H, S, Dk = q.shape
    Dv = v.shape[-1]
    W = min(chunk, S)
    assert S % W == 0
    n_c = S // W
    BH = B * H
    qf = q.reshape(BH, S, Dk)
    kf = k.reshape(BH, S, Dk)
    vf = v.reshape(BH, S, Dv)
    lif = li.reshape(BH, S)
    lff = lf.reshape(BH, S)
    from jax.experimental.pallas import tpu as pltpu

    h, cs, ns, ms = pl.pallas_call(
        functools.partial(_kernel, W=W, Dk=Dk, Dv=Dv, n_c=n_c),
        grid=(BH, n_c),
        in_specs=[
            pl.BlockSpec((1, W, Dk), lambda bh, cj: (bh, cj, 0)),
            pl.BlockSpec((1, W, Dk), lambda bh, cj: (bh, cj, 0)),
            pl.BlockSpec((1, W, Dv), lambda bh, cj: (bh, cj, 0)),
            pl.BlockSpec((1, W), lambda bh, cj: (bh, cj)),
            pl.BlockSpec((1, W), lambda bh, cj: (bh, cj)),
        ],
        out_specs=[
            pl.BlockSpec((1, W, Dv), lambda bh, cj: (bh, cj, 0)),
            pl.BlockSpec((1, Dk, Dv), lambda bh, cj: (bh, 0, 0)),
            pl.BlockSpec((1, 1, Dk), lambda bh, cj: (bh, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda bh, cj: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, Dv), jnp.float32),
            jax.ShapeDtypeStruct((BH, Dk, Dv), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, Dk), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Dk, Dv), jnp.float32),
            pltpu.VMEM((1, Dk), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, lif, lff)
    return (
        h.reshape(B, H, S, Dv),
        (cs.reshape(B, H, Dk, Dv), ns.reshape(B, H, Dk),
         ms.reshape(B, H)),
    )
