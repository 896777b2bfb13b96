"""Fused EMA sketch-update Pallas kernel (the paper's per-step hot spot).

The three updates (Eqs. 5a-5c) each contract the SAME activation matrix
A (T, d) against a thin (T, k) projection. Done naively that is three HBM
passes over A at arithmetic intensity k ~ 5-33 FLOP/byte — far below the
v5e ridge (~240), i.e. hard memory-bound. This kernel fuses all three
contractions plus the EMA accumulate into ONE pass over A: ~3x on the
dominant (memory) roofline term (DESIGN.md §7).

Tiling: grid (d_blocks, t_blocks), t innermost so each output block
(d_blk, k_pad) stays resident in VMEM across the T reduction. k is padded
to the 128-lane width; the logical k = 2r+1 columns beyond k_active are
zero by construction (projections are pre-masked by the caller).

    A block     (t_blk, d_blk)      read once, feeds all three dots
    proj blocks (t_blk, k_pad)      Upsilon/Omega/Phi
    X/Y/Z       (d_blk, k_pad)      EMA-initialized at j==0, accumulated
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_T_BLK = 256
DEFAULT_D_BLK = 256
LANE = 128


def _kernel(a_ref, ups_ref, omg_ref, phi_ref, psi_ref,
            x_in_ref, y_in_ref, z_in_ref,
            x_ref, y_ref, z_ref, *, beta: float, n_t_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        x_ref[...] = beta * x_in_ref[...]
        y_ref[...] = beta * y_in_ref[...]
        z_ref[...] = beta * z_in_ref[...]

    at = a_ref[...].astype(jnp.float32).T          # (d_blk, t_blk)
    scale = 1.0 - beta
    x_ref[...] += scale * jax.lax.dot(
        at, ups_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    y_ref[...] += scale * jax.lax.dot(
        at, omg_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    zc = jax.lax.dot(at, phi_ref[...].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    z_ref[...] += scale * zc * psi_ref[...].astype(jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("beta", "t_blk", "d_blk", "interpret"),
)
def sketch_update(a, x_s, y_s, z_s, ups, omg, phi, psi, *,
                  beta: float, t_blk: int = DEFAULT_T_BLK,
                  d_blk: int = DEFAULT_D_BLK, interpret: bool | None = None):
    """Fused EMA update. a (T, d); sketches (d, k); proj (T, k); psi (k,).

    k is padded to a multiple of 128 internally; ragged T/d are padded
    up to the block grid with zeros (zero activation rows contribute
    nothing to the contraction; padded d rows are sliced off). Outputs
    match the input sketch shapes exactly.
    """
    if interpret is None:
        from repro.kernels.ops import interpret_mode
        interpret = interpret_mode()
    T, d = a.shape
    k = x_s.shape[1]
    t_blk = min(t_blk, T)
    d_blk = min(d_blk, d)
    T_pad = -(-T // t_blk) * t_blk
    d_pad = -(-d // d_blk) * d_blk
    k_pad = -(-k // LANE) * LANE

    def pad_to(m, sizes):
        w = [(0, s - m.shape[i]) for i, s in enumerate(sizes)]
        return jnp.pad(m, w)

    a = pad_to(a, (T_pad, d_pad))
    x_p, y_p, z_p = (pad_to(m, (d_pad, k_pad)) for m in (x_s, y_s, z_s))
    ups_p, omg_p, phi_p = (pad_to(m, (T_pad, k_pad))
                           for m in (ups, omg, phi))
    psi_p = pad_to(psi[None, :], (1, k_pad))        # (1, k_pad)

    grid = (d_pad // d_blk, T_pad // t_blk)
    out_spec = pl.BlockSpec((d_blk, k_pad), lambda i, j: (i, 0))
    outs = pl.pallas_call(
        functools.partial(_kernel, beta=beta, n_t_blocks=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((t_blk, d_blk), lambda i, j: (j, i)),   # A
            pl.BlockSpec((t_blk, k_pad), lambda i, j: (j, 0)),   # ups
            pl.BlockSpec((t_blk, k_pad), lambda i, j: (j, 0)),   # omg
            pl.BlockSpec((t_blk, k_pad), lambda i, j: (j, 0)),   # phi
            pl.BlockSpec((1, k_pad), lambda i, j: (0, 0)),       # psi
            out_spec, out_spec, out_spec,                        # X/Y/Z in
        ],
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((d_pad, k_pad), jnp.float32)] * 3,
        interpret=interpret,
    )(a, ups_p, omg_p, phi_p, psi_p, x_p, y_p, z_p)
    return tuple(o[:d, :k] for o in outs)
