"""Chunked count-sketch heavy-hitter search Pallas kernel.

The dense recovery path (`countsketch.csvec.query_all` + `top_k`)
materializes an (r, dim) estimate matrix before selecting k winners —
for D ≫ 10M that is the memory wall the streaming pipeline removes.
This kernel sweeps the index space in fixed-size chunks and keeps only
a running (k,) best buffer:

  * the multiply-shift hashes (see countsketch/csvec.py) are recomputed
    in-register from the global coordinate index — nothing but the
    (r, c) table and (4, r) params ever leave HBM;
  * the per-row table lookup is the one-hot MXU trick in reverse of
    csvec_insert: a (chunk, c) one-hot bucket matrix contracted against
    the table row gathers all chunk estimates in one matmul;
  * the median over the r row estimates is an odd-even transposition
    sorting network (static r, min/max compare-exchanges only) — the
    sorted middle matches `jnp.median` bit-for-bit for odd r;
  * the running top-k is SORT-PRIMITIVE-FREE (no `lax.top_k`/`lax.sort`,
    which block Mosaic lowering): a bitonic compare-exchange network
    sorts each chunk by (|estimate| desc, index asc), and a bitonic
    MERGE network folds the chunk's top slice into the running best
    buffer (kept sorted under the same key). Partner pairing is pure
    reshape/flip — no gathers. The lexicographic tie-break reproduces
    `lax.top_k`'s stable earlier-index-wins semantics, so candidate
    selection matches the dense oracle `lax.top_k(|query_all|, k)`
    EXACTLY (tested in tests/test_countsketch.py).

Grid: (cdiv(dim, chunk),) over the coordinate space. The (1, k) best
value/index buffers live in the output refs and persist across the
sequential grid; tail-chunk padding indices estimate to -inf magnitude
and are never selected.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_CHUNK = 16384

_U32 = jnp.uint32
_IMAX = np.int32(np.iinfo(np.int32).max)


def _median_rows(est):
    """Median over a static list of r (chunk,) row estimates via an
    odd-even transposition sorting network (compare-exchange only)."""
    rows = list(est)
    r = len(rows)
    for rnd in range(r):
        for j in range(rnd % 2, r - 1, 2):
            lo = jnp.minimum(rows[j], rows[j + 1])
            hi = jnp.maximum(rows[j], rows[j + 1])
            rows[j], rows[j + 1] = lo, hi
    if r % 2:
        return rows[r // 2]
    return 0.5 * rows[r // 2 - 1] + 0.5 * rows[r // 2]


# ---------------------------------------------------------------------------
# Bitonic compare-exchange machinery (no lax.sort / lax.top_k)
# ---------------------------------------------------------------------------


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _partner_swap(v, stride: int):
    """v[i] <-> v[i ^ stride] via reshape + half-flip (gather-free)."""
    b = v.reshape(-1, 2, stride)
    return jnp.concatenate([b[:, 1], b[:, 0]], axis=1).reshape(v.shape)


def _compare_exchange(mag, val, idx, stride: int, keep_first):
    """One network stage. Elements where ``keep_first`` is True end up
    holding the pair member that comes FIRST in (mag desc, idx asc)
    order; the partner position holds the other."""
    pm = _partner_swap(mag, stride)
    pv = _partner_swap(val, stride)
    pi = _partner_swap(idx, stride)
    first = (mag > pm) | ((mag == pm) & (idx < pi))
    keep_self = jnp.where(keep_first, first, ~first)
    return (
        jnp.where(keep_self, mag, pm),
        jnp.where(keep_self, val, pv),
        jnp.where(keep_self, idx, pi),
    )


def _stage_iota(n: int):
    """In-kernel position index (Pallas forbids captured array consts)."""
    return jax.lax.broadcasted_iota(jnp.int32, (n,), 0)


def _bitonic_sort_desc(mag, val, idx):
    """Full bitonic sort of pow2-length arrays, descending by
    (mag, idx asc). Static O(log^2 n) compare-exchange stages."""
    n = mag.shape[0]
    i = _stage_iota(n)
    size = 2
    while size <= n:
        stride = size // 2
        while stride >= 1:
            desc = (i & size) == 0
            is_lower = (i & stride) == 0
            keep_first = is_lower == desc
            mag, val, idx = _compare_exchange(mag, val, idx, stride,
                                              keep_first)
            stride //= 2
        size *= 2
    return mag, val, idx


def _bitonic_merge_desc(mag, val, idx):
    """Merge a bitonic (desc-then-asc) pow2-length sequence into fully
    descending order — the running-merge half of the network."""
    n = mag.shape[0]
    i = _stage_iota(n)
    stride = n // 2
    while stride >= 1:
        keep_first = (i & stride) == 0
        mag, val, idx = _compare_exchange(mag, val, idx, stride,
                                          keep_first)
        stride //= 2
    return mag, val, idx


def _pad_desc(mag, val, idx, n: int):
    """Pad candidate triples to length n with never-selected sentinels
    (mag -inf, idx INT32_MAX so they sort after everything)."""
    pad = n - mag.shape[0]
    if pad <= 0:
        return mag, val, idx
    return (
        jnp.concatenate([mag, jnp.full((pad,), -jnp.inf, jnp.float32)]),
        jnp.concatenate([val, jnp.zeros((pad,), jnp.float32)]),
        jnp.concatenate([idx, jnp.full((pad,), _IMAX, jnp.int32)]),
    )


def _kernel(par_ref, tab_ref, val_ref, idx_ref, *,
            dim: int, rows: int, k: int, shift: int, chunk: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        val_ref[...] = jnp.zeros((1, k), jnp.float32)
        idx_ref[...] = -jnp.ones((1, k), jnp.int32)

    c = tab_ref.shape[1]
    gidx = (i * chunk + jax.lax.broadcasted_iota(
        jnp.int32, (chunk, 1), 0))                           # (chunk, 1)
    gu = gidx.astype(_U32)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    est_rows = []
    for j in range(rows):
        ab, bb = par_ref[0, j], par_ref[1, j]
        asg, bsg = par_ref[2, j], par_ref[3, j]
        bucket = ((ab * gu + bb) >> _U32(shift)).astype(jnp.int32)
        sbit = ((asg * gu + bsg) >> _U32(31)).astype(jnp.float32)
        sgn = 1.0 - 2.0 * sbit                               # (chunk, 1)
        onehot = (bucket == col_iota).astype(jnp.float32)    # (chunk, c)
        looked = jax.lax.dot(
            onehot, tab_ref[j:j + 1, :].reshape(c, 1),
            preferred_element_type=jnp.float32)              # (chunk, 1)
        est_rows.append((sgn * looked).reshape(chunk))
    est = _median_rows(est_rows)                             # (chunk,)

    neg_inf = jnp.float32(-jnp.inf)
    cidx = gidx.reshape(chunk)
    mag = jnp.where(cidx < dim, jnp.abs(est), neg_inf)

    # chunk-local top-k: pad to pow2, full bitonic sort, slice the head
    kp = _next_pow2(k)
    cm, cv, ci = _pad_desc(mag, est, cidx, _next_pow2(chunk))
    cm, cv, ci = _bitonic_sort_desc(cm, cv, ci)
    cm, cv, ci = cm[:kp], cv[:kp], ci[:kp]

    # running merge: best buffer is kept sorted under the same key, so
    # [best, reversed(chunk_top)] is bitonic — one merge network folds it
    bvals = val_ref[0, :]
    bidx = idx_ref[0, :]
    bmag = jnp.where(bidx >= 0, jnp.abs(bvals), neg_inf)
    bm, bv, bi = _pad_desc(bmag, bvals, bidx, kp)
    mm = jnp.concatenate([bm, cm[::-1]])
    mv = jnp.concatenate([bv, cv[::-1]])
    mi = jnp.concatenate([bi, ci[::-1]])
    mm, mv, mi = _bitonic_merge_desc(mm, mv, mi)
    val_ref[0, :] = mv[:k]
    idx_ref[0, :] = mi[:k]


@functools.partial(jax.jit,
                   static_argnames=("dim", "k", "chunk", "interpret"))
def csvec_topk(table, params, *, dim: int, k: int,
               chunk: int = DEFAULT_CHUNK, interpret: bool | None = None):
    """table (r, c) f32; params (4, r) u32; returns (vals (k,) f32,
    idx (k,) i32) — the top-k coordinates of the sketched vector by
    |median-of-r estimate|, descending, peak memory O(chunk + k).
    Matches `countsketch.csvec.topk_streaming` (parity tested)."""
    if interpret is None:
        from repro.kernels.ops import interpret_mode
        interpret = interpret_mode()
    r, c = table.shape
    log2c = c.bit_length() - 1
    assert c == (1 << log2c), f"cols must be a power of two, got {c}"
    k = min(k, dim)
    chunk = min(chunk, max(128, dim))
    chunk = max(chunk, k)      # the chunk-local sort must cover k heads
    grid = (-(-dim // chunk),)
    vals, idx = pl.pallas_call(
        functools.partial(_kernel, dim=dim, rows=r, k=k,
                          shift=32 - log2c, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((4, r), lambda i: (0, 0)),          # hash params
            pl.BlockSpec((r, c), lambda i: (0, 0)),          # table
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
        ],
        interpret=interpret,
    )(params, table)
    return vals.reshape(k), idx.reshape(k)
