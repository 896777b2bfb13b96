"""SketchedSGD-style gradient compression over a count sketch.

Per step (Ivkin et al., adapted from /root/related mmathys/sketchedsgd):

    u <- m * u + g                    sketch-space momentum accumulator
    v <- v + u                        error-feedback accumulator
    S <- CSVec.insert(0, v)           one linear sketch of the residual
    S <- psum(S, dp_axis)             EXACT merge (linearity) — round 1
                                      on the DP wire: r*c floats
    cand <- streaming_topk(S, p2*k)   chunked heavy-hitter search: peak
                                      memory O(chunk + k), never the
                                      (D,) estimate vector
    vals <- psum(v[cand]) / W         round 2 (cs_p2 > 0): exact residual
                                      values at the candidates de-noise
                                      the sketch estimates — p2*k floats
                                      on the wire (indices are derived
                                      identically by every worker)
    update <- top_k(vals, k)          final k winners
    v <- v - update                   unsent mass stays local and
    u <- u * (1 - transmitted)        re-injects next step

Because the sketch is linear, momentum/error-feedback on the dense
accumulator commute with sketching: sketching v is identical to keeping
momentum in sketch space (m * S_u + S_g) — we keep the dense accumulator
because `unsketch` needs residual subtraction at transmitted coords.

Residual subtraction (v - update) rather than coordinate zeroing keeps
even the sketch ESTIMATION error in v, so it is corrected on a later
step — and makes mass conservation exact:  v_new + update == v_old + u
(tested in tests/test_countsketch.py).

int8 wire (DESIGN.md §9): with cfg.wire_dtype == "int8" the table is
symmetrically per-row quantized BEFORE the merge. What crosses the wire
is the int8 counters + r f32 scales (~4x fewer bytes); what the merged
sum is built from is each worker's dequantized grid values — the psum
of dequantized tables here is value-identical to an int8 all-gather
followed by local dequant-sum on a real interconnect. The per-worker
quantization residual (table - dequant) never leaves the worker: the
transmitted update is reconstructed from quantized information only, so
``v_new = v_pre - update`` keeps the full quantization error inside the
error-feedback accumulator, to be re-sent on a later step — the same
mechanism that already absorbs sketch estimation error. The symmetric
(zero-point-free) grid keeps the merged estimate unbiased: a psum of W
affine-quantized tables would accumulate W zero-point offsets.

The compression is split at the collective boundary so the fused
one-psum-per-step path (train/step.py) can ride the table on the same
flat buffer as the EMA sketch increments:

    local  = countsketch_local(grads, err, cfg)     # sketch + quantize
    merged = <any exact table merge>                # psum / flat psum
    out    = countsketch_finish(local, merged, ...) # recover + update

Under the overlap schedule (DESIGN.md §10) the same split holds at the
PHASE-2 boundary: the gradients only exist after the backward, so
`countsketch_local` — including the int8 symmetric quantize whose
residual stays in the per-worker error feedback — runs after the
backward sweep and the table rides the LATE psum, while the sketch
increments already crossed on the early one. Nothing about the
quantize/dequantize/residual rule changes with the schedule.

Everything is flat-vector space: the gradient pytree is raveled once,
compressed, and unraveled — static shapes, jit/shard_map friendly.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

Array = jax.Array

from repro.countsketch.csvec import (
    CSVec, dequantize_table, insert, make_csvec, quantize_table,
    quantized_table_bytes, table_bytes, topk_streaming,
)
from repro.kernels.csvec_insert import csvec_insert
from repro.kernels.csvec_topk import csvec_topk
from repro.kernels import pallas_enabled


def flat_dim(params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))


def init_countsketch_state(params):
    """Dense flat momentum (u) and error-feedback (v) accumulators."""
    d = flat_dim(params)
    return {"u": jnp.zeros(d, jnp.float32), "v": jnp.zeros(d, jnp.float32)}


def grad_csvec(cfg, dim: int) -> CSVec:
    """The step's (empty) sketch. Derived from a config-seeded key, so
    every DP worker builds the SAME hash family — the precondition for
    exact psum merging. Never carried in the train state: the table is
    recreated zero each step, hash params are pure functions of cfg."""
    return make_csvec(
        jax.random.PRNGKey(cfg.cs_seed), dim, cfg.cs_rows, cfg.cs_cols)


def _sketch_residual(cs: CSVec, v, cfg):
    if pallas_enabled():
        table = csvec_insert(cs.table, cs.params, v)
        return CSVec(table=table, params=cs.params, dim=cs.dim)
    return insert(cs, v)


def _recover_candidates(cs: CSVec, k: int, cfg):
    """Streaming heavy-hitter nomination from the merged sketch: top-k
    coordinates by |median estimate| in O(chunk + k) peak memory (vals
    descending; identical on every worker — the sketch was psum-merged,
    so no index exchange is ever needed)."""
    if pallas_enabled():
        return csvec_topk(cs.table, cs.params, dim=cs.dim, k=k,
                          chunk=cfg.cs_chunk)
    return topk_streaming(cs, k, chunk=cfg.cs_chunk)


@dataclasses.dataclass
class CountsketchLocal:
    """Worker-local compression state at the collective boundary: the
    wire-ready sketch plus everything `countsketch_finish` needs. Lives
    entirely inside one traced step — never a jit boundary pytree."""

    cs: CSVec           # table holds the WIRE values (dequantized grid
    #                     values under wire_dtype="int8", raw f32 else).
    #                     The quantization error table - dequant never
    #                     needs materializing: the update is recovered
    #                     from quantized information only, so residual
    #                     subtraction v_new = v_pre - update retains it
    #                     in v implicitly (csvec.quantize_residual is
    #                     the explicit form the property tests check)
    v_pre: Array        # dense error-feedback residual incl. this grad
    u: Array            # momentum accumulator
    unravel: Any        # flat -> grads pytree
    cfg: Any            # geometry-resolved CompressionConfig
    dim: int


def countsketch_local(grads, err_state, cfg) -> CountsketchLocal:
    """Everything BEFORE the table merge: momentum + error feedback in
    dense space, the linear sketch of the residual, and (int8 wire) the
    symmetric per-row quantize/dequantize whose error stays local."""
    from repro.optim.compression import resolve_countsketch

    flat, unravel = ravel_pytree(grads)
    flat = flat.astype(jnp.float32)
    dim = flat.shape[0]
    cfg = resolve_countsketch(cfg, dim)
    u = cfg.cs_momentum * err_state["u"] + flat
    v_pre = err_state["v"] + u

    cs = _sketch_residual(grad_csvec(cfg, dim), v_pre, cfg)
    if cfg.wire_dtype == "int8":
        if pallas_enabled():
            from repro.kernels.csvec_quant import csvec_quant
            _, _, dhat, _ = csvec_quant(cs.table)
        else:
            q, scale = quantize_table(cs.table)
            dhat = dequantize_table(q, scale)
        cs = dataclasses.replace(cs, table=dhat)
    return CountsketchLocal(cs=cs, v_pre=v_pre, u=u, unravel=unravel,
                            cfg=cfg, dim=dim)


def countsketch_nominate(local: CountsketchLocal, merged: CSVec):
    """Phase A of the p2 exact-value round (cs_p2 > 0): heavy-hitter
    candidate nomination from the merged table plus THIS worker's exact
    residual values at those candidates — the p2 wire payload. Split
    out of `countsketch_finish` so the flat-wire step can issue the p2
    psum and overlap the dense optimizer pass with it (DESIGN.md §14);
    finish composes nominate -> psum -> complete, so the serial path
    runs bitwise the same ops. Candidates are identical on every worker
    (`merged` is the collective's output), so no index exchange."""
    cfg, dim = local.cfg, local.dim
    n_cand = min(cfg.cs_p2 * min(cfg.cs_k, dim), dim)
    _, cand = _recover_candidates(merged, n_cand, cfg)
    return cand, local.v_pre[cand]


def countsketch_complete(local: CountsketchLocal, merged: CSVec,
                         cand, exact, *, workers):
    """Phase B, after the p2 collective: top-k winner selection from
    the MERGED exact residual values, the transmitted update, and the
    new {u, v} error-feedback state. Returns
    ``(update (dim,) flat, sel_idx (k,), sel_val (k,), state, stats)``
    — the FLAT update plus the winner coordinates, so the overlapped
    optimizer (optim/adamw.adamw_sparse_update) can correct exactly k
    entries of its zero-grad dense pass."""
    cfg, dim, v_pre, u = local.cfg, local.dim, local.v_pre, local.u
    k = min(cfg.cs_k, dim)
    exact = exact / workers
    _, pos = jax.lax.top_k(jnp.abs(exact), k)
    sel_idx, sel_val = cand[pos], exact[pos]

    update = jnp.zeros(dim, jnp.float32).at[sel_idx].set(sel_val)
    sent = (update != 0.0).astype(jnp.float32)
    # residual subtraction (not coordinate zeroing): v keeps sketch
    # estimation error AND, under the int8 wire, the quantization error
    # baked into `update` — both re-inject on a later step; mass
    # conservation v_new + update == v_pre holds to one rounding at the
    # k transmitted coordinates and bit-exactly everywhere else
    new_v = v_pre - update
    new_u = u * (1.0 - sent)

    wire = (quantized_table_bytes(merged)
            if cfg.wire_dtype == "int8" else table_bytes(merged))
    wire += cand.shape[0] * 4
    stats = {
        "wire_bytes": float(wire),
        "compression_ratio": wire / (dim * 4),
    }
    return update, sel_idx, sel_val, {"u": new_u, "v": new_v}, stats


def countsketch_finish(local: CountsketchLocal, merged: CSVec, *,
                       workers, axis_name: str | None = None):
    """Everything AFTER the table merge: heavy-hitter recovery from the
    merged table (+ optional p2 exact-value round over `axis_name`),
    the transmitted update, and the new {u, v} error-feedback state.

    `workers` is the DP axis size (traced or static); `merged` must be
    identical on every worker (the caller's collective contract), so
    candidate selection needs no index exchange."""
    cfg, dim, v_pre, u = local.cfg, local.dim, local.v_pre, local.u
    k = min(cfg.cs_k, dim)
    if cfg.cs_p2 > 0:
        cand, exact = countsketch_nominate(local, merged)
        if axis_name is not None:
            from repro.parallel.collectives import traced_psum
            exact = traced_psum(exact, axis_name, name="cs_p2_values")
        update, _, _, new_state, stats = countsketch_complete(
            local, merged, cand, exact, workers=workers)
        return local.unravel(update), new_state, stats

    est, sel_idx = _recover_candidates(merged, k, cfg)
    sel_val = est / workers
    update = jnp.zeros(dim, jnp.float32).at[sel_idx].set(sel_val)
    sent = (update != 0.0).astype(jnp.float32)
    # same residual-subtraction rule as `countsketch_complete`
    new_v = v_pre - update
    new_u = u * (1.0 - sent)

    wire = (quantized_table_bytes(merged)
            if cfg.wire_dtype == "int8" else table_bytes(merged))
    stats = {
        "wire_bytes": float(wire),
        "compression_ratio": wire / (dim * 4),
    }
    return (local.unravel(update), {"u": new_u, "v": new_v}, stats)


def compress_grads_countsketch(grads, err_state, cfg, *,
                               axis_name: str | None = None):
    """Returns (compressed grads pytree, new {u, v} state, stats).

    With `axis_name` set (inside shard_map/pmap over the DP axis) the
    O(r*c) sketch table is psum-merged instead of the O(D) dense
    gradient; without it the path is the single-worker special case
    (W=1, psum = identity) used under plain jit. With cfg.cs_p2 > 0 a
    second O(p2*k) collective fetches the exact summed residual values
    at the nominated candidates (SketchedSGD's p2 exchange), removing
    sketch estimation noise from the transmitted coordinates.

    This is the PER-NODE collective layout (one psum for the table, one
    for p2); the fused one-collective-per-step path in train/step.py
    calls `countsketch_local` / `countsketch_finish` directly and rides
    the table on the step's single flat-segment psum."""
    local = countsketch_local(grads, err_state, cfg)
    merged = local.cs
    workers = 1.0
    if axis_name is not None:
        from repro.parallel.collectives import psum_csvec
        merged = psum_csvec(local.cs, axis_name)
        workers = jax.lax.psum(1.0, axis_name)
    return countsketch_finish(local, merged, workers=workers,
                              axis_name=axis_name)


def countsketch_wire_bytes(cfg, num_params: int = 0) -> int:
    """Per-step, per-worker bytes on the DP all-reduce wire (delegates
    to the single source of truth in optim/compression.py). The table
    size is independent of the parameter count once resolved — but an
    auto-sized config (cs_cols=None) needs `num_params` to resolve its
    geometry first."""
    from repro.optim.compression import compressed_bytes
    return compressed_bytes(num_params, cfg)
