"""Fused count-sketch insert Pallas kernel (DP-compression hot path).

A naive CSVec insert of a length-n gradient is r scatter-adds — on TPU
that lowers to r serialized HBM passes with 1-element transactions. This
kernel makes ONE HBM pass over the flattened gradient and updates all r
hash rows on the fly:

  * the multiply-shift hashes (see countsketch/csvec.py) are recomputed
    in-register from the global element index — no (r, n) bucket/sign
    tables ever touch HBM;
  * the scatter becomes an MXU matmul: a (blk, c) one-hot bucket matrix
    contracted against the signed values gives the per-row bucket sums
    (one-hot @ MXU is the canonical TPU scatter trick);
  * the (r, c) table stays resident in VMEM across the whole grid (r*c
    floats ~ tens of KB), initialized from the input table at step 0 and
    accumulated over vector blocks.

Grid: (n_blocks,) over the padded flat vector. Zero padding is free:
padded elements carry value 0 and contribute nothing to any bucket.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLK = 2048

_U32 = jnp.uint32


def _kernel(vec_ref, par_ref, tin_ref, out_ref, *,
            rows: int, shift: int, blk: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = tin_ref[...]

    c = out_ref.shape[1]
    v = vec_ref[...].astype(jnp.float32)                    # (1, blk)
    # global element index of each lane in this block, as wrapping u32
    gidx = (i * blk + jax.lax.broadcasted_iota(
        jnp.int32, (blk, 1), 0)).astype(_U32)               # (blk, 1)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    for j in range(rows):
        ab, bb = par_ref[0, j], par_ref[1, j]
        asg, bsg = par_ref[2, j], par_ref[3, j]
        bucket = ((ab * gidx + bb) >> _U32(shift)).astype(jnp.int32)
        sbit = ((asg * gidx + bsg) >> _U32(31)).astype(jnp.float32)
        sgn = 1.0 - 2.0 * sbit                              # (blk, 1)
        onehot = (bucket == col_iota).astype(jnp.float32)   # (blk, c)
        sv = sgn * v.reshape(blk, 1)                        # (blk, 1)
        out_ref[j:j + 1, :] += jax.lax.dot(
            sv.reshape(1, blk), onehot,
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def csvec_insert(table, params, vec, *,
                 blk: int = DEFAULT_BLK, interpret: bool | None = None):
    """table (r, c) f32; params (4, r) u32; vec (n,) — returns the
    accumulated (r, c) table. Matches `countsketch.csvec.insert` on the
    shared hash family (parity tested in tests/test_countsketch.py)."""
    if interpret is None:
        from repro.kernels.ops import interpret_mode
        interpret = interpret_mode()
    r, c = table.shape
    log2c = c.bit_length() - 1
    assert c == (1 << log2c), f"cols must be a power of two, got {c}"
    n = vec.shape[0]
    blk = min(blk, max(128, n))
    n_pad = -(-n // blk) * blk
    vp = jnp.pad(vec.astype(jnp.float32), (0, n_pad - n))
    vp = vp.reshape(n_pad // blk, blk)

    grid = (n_pad // blk,)
    out = pl.pallas_call(
        functools.partial(_kernel, rows=r, shift=32 - log2c, blk=blk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk), lambda i: (i, 0)),       # vec block
            pl.BlockSpec((4, r), lambda i: (0, 0)),         # hash params
            pl.BlockSpec((r, c), lambda i: (0, 0)),         # table in
        ],
        out_specs=pl.BlockSpec((r, c), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.float32),
        interpret=interpret,
    )(vp, params, table)
    return out
