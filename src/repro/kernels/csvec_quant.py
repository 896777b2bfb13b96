"""Fused int8 quantize/dequantize Pallas kernel for the count-sketch
wire (DESIGN.md §9).

The (r, c) sketch table is tiny (tens of KB) but sits on the DP hot
path every step: quantizing it on the way to the collective must not
cost an extra HBM round-trip per stage (amax, scale, round, clip,
dequant, residual would be six element-wise passes under naive XLA
fusion boundaries). This kernel keeps the whole table resident in VMEM
and produces, in ONE pass:

  * ``q``     (r, c) int8  — the symmetric per-row quantized counters
                             (the bytes a real interconnect ships);
  * ``scale`` (r, 1) f32   — per-row grids, amax/127;
  * ``dhat``  (r, c) f32   — the dequantized table, i.e. the exact
                             values the merged sum is built from (the
                             psum simulation operand);
  * ``resid`` (r, c) f32   — table - dhat, the worker-local
                             quantization error retained by the
                             SketchedSGD error feedback.

Rounding is round-nearest-even to match the `jnp.round` reference in
`countsketch/csvec.py`: q, scale and dhat are bit-exact against the
reference; resid may differ by one ulp of the row amax when XLA
contracts the final multiply-subtract into an FMA (parity tested in
tests/test_countsketch.py). All-zero rows emit scale 0 and quantize
losslessly to zeros (the reference's convention).

Grid: (1,) — the table is far below VMEM capacity for every geometry
`resolve_countsketch` admits; rows are vectorized, not looped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# the ONE symmetric grid constant — shared with the jnp reference the
# kernel is bit-parity-tested against
from repro.countsketch.csvec import QMAX


def _kernel(tab_ref, q_ref, scale_ref, dhat_ref, resid_ref):
    t = tab_ref[...].astype(jnp.float32)                     # (r, c)
    amax = jnp.max(jnp.abs(t), axis=1, keepdims=True)        # (r, 1)
    scale = amax / QMAX
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.clip(jnp.round(t / safe), -QMAX, QMAX)
    dhat = q * scale
    scale_ref[...] = scale
    q_ref[...] = q.astype(jnp.int8)
    dhat_ref[...] = dhat
    # XLA may contract t - q*scale into an FMA (one rounding instead of
    # two) — resid can differ from the eager reference by one ulp of
    # the row amax, never more; q/scale/dhat are bit-exact
    resid_ref[...] = t - dhat


@functools.partial(jax.jit, static_argnames=("interpret",))
def csvec_quant(table, *, interpret: bool | None = None):
    """table (r, c) f32 -> (q (r, c) i8, scale (r,) f32,
    dhat (r, c) f32, resid (r, c) f32), all from one VMEM-resident pass.

    Matches `countsketch.csvec.quantize_table` / `dequantize_table` /
    `quantize_residual` bit-for-bit.
    """
    if interpret is None:
        from repro.kernels.ops import interpret_mode
        interpret = interpret_mode()
    r, c = table.shape
    q, scale, dhat, resid = pl.pallas_call(
        _kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((r, c), lambda i: (0, 0))],
        out_specs=(
            pl.BlockSpec((r, c), lambda i: (0, 0)),
            pl.BlockSpec((r, 1), lambda i: (0, 0)),
            pl.BlockSpec((r, c), lambda i: (0, 0)),
            pl.BlockSpec((r, c), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((r, c), jnp.int8),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
            jax.ShapeDtypeStruct((r, c), jnp.float32),
            jax.ShapeDtypeStruct((r, c), jnp.float32),
        ),
        interpret=interpret,
    )(table.astype(jnp.float32))
    return q, scale.reshape(r), dhat, resid


def csvec_quant_ref(table):
    """Pure-jnp oracle with the same signature (delegates to the
    canonical reference in countsketch/csvec.py)."""
    from repro.countsketch.csvec import (
        dequantize_table, quantize_residual, quantize_table,
    )

    q, scale = quantize_table(table)
    dhat = dequantize_table(q, scale)
    return q, scale, dhat, quantize_residual(table, q, scale)
