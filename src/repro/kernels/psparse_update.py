"""p-sparsified EMA sketch-update Pallas kernel (DESIGN.md §13).

The dense path multiplies every activation batch A (T, d) against three
dense Gaussian (T, k) projections — the largest FLOP + HBM-read term
left in the sketched hot path. This kernel replaces the dense matrices
by a p-sparsified projection that is never materialized in HBM: a
shared-support sampled-Rademacher construction

    Omega[t, j] = alpha * sgn(u, j)   if t == row(u) for some u < m,
                  0                   otherwise,

with m = max(k_max, round(p * T)) support rows (the max keeps the
sketch full-rank at tiny token counts), alpha = sqrt(T / m)
(= 1/sqrt(p_eff), unit per-entry variance — matching the unnormalized
dense-Gaussian convention of this repo; see DESIGN.md §13 for why the
p-sparsified papers' 1/sqrt(p*k) normalization does not apply here),
and row/sign both MULTIPLY-SHIFT hashes (Dietzfelbinger et al., the
`countsketch/csvec.py` family):

    row(u)    = ((a1*u + b1) >> 16) * T >> 16          in [0, T)
    sgn(u, j) = 1 - 2 * ((a2*(u<<16|j) + b2) >> 31)    in {-1, +1}

All hash arithmetic is uint32 with natural wraparound — exactly
computable in jnp, NumPy and inside a Pallas kernel, so the kernel, the
jnp tile-mirror reference (`psparse_update_ref`, bit-identical in
interpret mode) and the dense materializer (`psparse_dense`) agree on
the implicit matrix bit for bit.

Three consumers, one hash family:
  * `psparse_update`          — fused Pallas kernel: per (d, t) tile the
    (t_blk, k) projection tiles are regenerated in-register (one-hot
    MXU dot, the csvec_insert scatter trick) and contracted against the
    A tile; only A is read from HBM, pushing the update from the
    compute-bound region to the memory-bound floor (DESIGN.md §7/§13).
  * `psparse_update_ref`      — jnp oracle mirroring the kernel's exact
    t-block accumulation order (the CPU/differential reference).
  * `psparse_triple_increment`— the production jnp fast path: gather
    the m support rows of A once and contract against the small
    (m, k) sign matrix — p_eff of the dense FLOPs, all inside BLAS/MXU
    dots (the measured >= 3x of benchmarks/bench_kernels.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_T_BLK = 256
DEFAULT_D_BLK = 256
LANE = 128

_U32 = jnp.uint32


# ---------------------------------------------------------------------------
# Hash family (static geometry + uint32 multiply-shift coefficients)
# ---------------------------------------------------------------------------


def psparse_dim(num_tokens: int, k_max: int, density: float) -> int:
    """Support size m = clamp(round(p * T), k_max, T). The k_max floor
    keeps the implicit (T, k_max) matrix full column rank (the sketch
    would otherwise lose rank at tiny token counts); the T ceiling makes
    density=1 the all-rows limit."""
    return int(min(num_tokens, max(k_max, round(density * num_tokens))))


def psparse_scale(num_tokens: int, m: int) -> float:
    """alpha = sqrt(T/m) = 1/sqrt(p_eff): every implicit entry has unit
    variance, matching the unnormalized dense N(0,1) convention (the
    reconstruction in core/reconstruct.py is linear in this scale)."""
    return math.sqrt(num_tokens / m)


def psparse_hash_params(key, rows: int = 3):
    """(rows, 4) uint32 multiply-shift coefficients, one row per
    projection matrix: [a_row, b_row, a_sign, b_sign]. Multipliers are
    forced odd (2-universality), exactly like `countsketch.make_csvec`."""
    params = jax.random.bits(key, (rows, 4), _U32)
    return params.at[:, 0].set(params[:, 0] | _U32(1)) \
                 .at[:, 2].set(params[:, 2] | _U32(1))


def psparse_rows(params_m, m: int, num_tokens: int):
    """(m,) int32 support rows in [0, num_tokens) — top-16-bit Lemire
    reduction of the multiply-shift hash (pure uint32, no modulo)."""
    u = jnp.arange(m, dtype=_U32)
    h = params_m[0] * u + params_m[1]
    return (((h >> _U32(16)) * _U32(num_tokens)) >> _U32(16)) \
        .astype(jnp.int32)


def psparse_signs(params_m, m: int, k: int):
    """(m, k) f32 in {-1, +1} from the top bit of the sign hash of the
    packed (u << 16 | j) index."""
    uu = jnp.arange(m, dtype=_U32)[:, None] << _U32(16)
    jj = jnp.arange(k, dtype=_U32)[None, :]
    bit = ((params_m[2] * (uu | jj) + params_m[3]) >> _U32(31)) \
        .astype(jnp.float32)
    return 1.0 - 2.0 * bit


def psparse_dense_one(params_m, num_tokens: int, k: int, m: int):
    """One implicit (T, k) matrix, materialized densely via the same
    one-hot contraction the kernel computes per tile — every element is
    the identical dot over the m support slots, so this is bit-identical
    to the kernel's in-register generation (duplicated support rows add,
    CountSketch-style)."""
    rows = psparse_rows(params_m, m, num_tokens)
    sgn = psparse_signs(params_m, m, k) * psparse_scale(num_tokens, m)
    onehot = (rows[None, :] ==
              jnp.arange(num_tokens, dtype=jnp.int32)[:, None]
              ).astype(jnp.float32)                          # (T, m)
    return jax.lax.dot(onehot, sgn,
                       preferred_element_type=jnp.float32)


def psparse_dense(params, num_tokens: int, k: int, m: int) -> dict:
    """{"upsilon","omega","phi"}: the three implicit (T, k) matrices."""
    return {
        name: psparse_dense_one(params[i], num_tokens, k, m)
        for i, name in enumerate(("upsilon", "omega", "phi"))
    }


# ---------------------------------------------------------------------------
# Production jnp fast path: gather the support rows, contract small
# ---------------------------------------------------------------------------


def psparse_triple_increment(a, params, psi, beta: float, m: int,
                             dtype=jnp.float32):
    """Worker-LOCAL (1-beta)-scaled increments of one EMA triple update
    against the implicit projections — WITHOUT materializing them:
    A^T Omega = A[rows]^T (alpha * sgn), a gather of the m support rows
    plus a (d, m) @ (m, k) dot, i.e. p_eff of the dense FLOPs entirely
    inside BLAS/MXU dots. psi arrives pre-masked (k,); column masking is
    applied to the sign matrices (masking a projection column IS masking
    that increment column). Summation order differs from the kernel
    (allclose, not bitwise — same situation as the dense jnp-vs-kernel
    pair); across DP layouts this path is bitwise with itself, which is
    what the differential tier holds."""
    T = a.shape[0]
    k = psi.shape[-1]
    alpha = psparse_scale(T, m)
    a = jax.lax.stop_gradient(a).astype(dtype)
    scale = (1.0 - beta) * alpha
    outs = []
    for i in range(3):
        rows = psparse_rows(params[i], m, T)
        sgn = psparse_signs(params[i], m, k).astype(dtype)
        c = jax.lax.dot(a[rows].T, sgn,
                        preferred_element_type=dtype)
        outs.append(scale * c)
    inc_x, inc_y, inc_z = outs
    return inc_x, inc_y, inc_z * psi[None, :]


# ---------------------------------------------------------------------------
# Fused Pallas kernel: regenerate the projection tiles in-register
# ---------------------------------------------------------------------------


def _gen_tile(par_ref, mat: int, t0, t_blk: int, k_pad: int, m: int,
              num_tokens: int, alpha: float):
    """(t_blk, k_pad) projection tile for matrix `mat`, regenerated from
    the hash coefficients: one-hot(row(u) == t) @ (alpha * sgn(u, j)) —
    the csvec_insert one-hot MXU scatter trick, nothing read from HBM."""
    u = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1).astype(_U32)
    a1, b1 = par_ref[mat, 0], par_ref[mat, 1]
    a2, b2 = par_ref[mat, 2], par_ref[mat, 3]
    rows = (((a1 * u + b1) >> _U32(16)) * _U32(num_tokens)
            >> _U32(16)).astype(jnp.int32)                  # (1, m)
    t_iota = t0 + jax.lax.broadcasted_iota(jnp.int32, (t_blk, 1), 0)
    onehot = (rows == t_iota).astype(jnp.float32)           # (t_blk, m)
    uu = jax.lax.broadcasted_iota(jnp.int32, (m, k_pad), 0) \
        .astype(_U32) << _U32(16)
    jj = jax.lax.broadcasted_iota(jnp.int32, (m, k_pad), 1).astype(_U32)
    bit = ((a2 * (uu | jj) + b2) >> _U32(31)).astype(jnp.float32)
    sgn = alpha * (1.0 - 2.0 * bit)                         # (m, k_pad)
    return jax.lax.dot(onehot, sgn,
                       preferred_element_type=jnp.float32)


def _finalize(beta: float, scale: float, s_in, acc, psi=None):
    """out = beta * s_in + (1 - beta) * acc [* psi] with an
    optimization_barrier around every node: the decay multiply, the
    scale multiply and the final add each round independently, so the
    kernel and `psparse_update_ref` — which share this helper — cannot
    be driven apart by FMA/fusion choices XLA makes for one of the two
    surrounding programs (the source of 1-ulp drift otherwise)."""
    decay = jax.lax.optimization_barrier(beta * s_in)
    upd = jax.lax.optimization_barrier(scale * acc)
    if psi is not None:
        upd = jax.lax.optimization_barrier(upd * psi)
    return decay + upd


def _kernel(a_ref, par_ref, psi_ref, x_in_ref, y_in_ref, z_in_ref,
            x_ref, y_ref, z_ref, *, beta: float, m: int,
            num_tokens: int, alpha: float, t_blk: int):
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    k_pad = x_ref.shape[1]
    at = a_ref[...].astype(jnp.float32).T          # (d_blk, t_blk)
    gen = functools.partial(_gen_tile, par_ref, t0=j * t_blk,
                            t_blk=t_blk, k_pad=k_pad, m=m,
                            num_tokens=num_tokens, alpha=alpha)
    dx = jax.lax.dot(at, gen(0), preferred_element_type=jnp.float32)
    dy = jax.lax.dot(at, gen(1), preferred_element_type=jnp.float32)
    dz = jax.lax.dot(at, gen(2), preferred_element_type=jnp.float32)

    # the out buffers carry the RAW running sum of per-block dots; all
    # beta/scale arithmetic happens exactly once in the finalize step
    @pl.when(j == 0)
    def _init():
        x_ref[...] = dx
        y_ref[...] = dy
        z_ref[...] = dz

    @pl.when(j > 0)
    def _accum():
        x_ref[...] += dx
        y_ref[...] += dy
        z_ref[...] += dz

    @pl.when(j == nb - 1)
    def _fin():
        scale = 1.0 - beta
        psi = psi_ref[...].astype(jnp.float32)
        x_ref[...] = _finalize(beta, scale,
                               x_in_ref[...].astype(jnp.float32),
                               x_ref[...])
        y_ref[...] = _finalize(beta, scale,
                               y_in_ref[...].astype(jnp.float32),
                               y_ref[...])
        z_ref[...] = _finalize(beta, scale,
                               z_in_ref[...].astype(jnp.float32),
                               z_ref[...], psi)


@functools.partial(
    jax.jit,
    static_argnames=("beta", "m", "t_blk", "d_blk", "interpret"),
)
def psparse_update(a, x_s, y_s, z_s, params, psi, *, beta: float,
                   m: int, t_blk: int = DEFAULT_T_BLK,
                   d_blk: int = DEFAULT_D_BLK, interpret: bool | None = None):
    """Fused psparse EMA update. a (T, d); sketches (d, k); params
    (3, 4) uint32; psi (k,) pre-masked. Same padding contract as
    `kernels.sketch_update`: k is padded to the 128-lane width, ragged
    T/d pad with zeros (zero activation rows contribute nothing; the
    generated tile rows beyond T are irrelevant against them), outputs
    match the input sketch shapes. Column masking is the caller's.
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

    def pad_to(mtx, sizes):
        w = [(0, s - mtx.shape[i]) for i, s in enumerate(sizes)]
        return jnp.pad(mtx, w)

    a = pad_to(a, (T_pad, d_pad))
    x_p, y_p, z_p = (pad_to(s, (d_pad, k_pad)) for s in (x_s, y_s, z_s))
    psi_p = pad_to(psi[None, :], (1, k_pad))        # (1, k_pad)

    grid = (d_pad // d_blk, T_pad // t_blk)
    out_spec = pl.BlockSpec((d_blk, k_pad), lambda i, j: (i, 0))
    outs = pl.pallas_call(
        functools.partial(
            _kernel, beta=beta, m=m, num_tokens=T,
            alpha=psparse_scale(T, m), t_blk=t_blk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((t_blk, d_blk), lambda i, j: (j, i)),   # A
            pl.BlockSpec((3, 4), lambda i, j: (0, 0)),   # hash params
            pl.BlockSpec((1, k_pad), lambda i, j: (0, 0)),       # psi
            out_spec, out_spec, out_spec,                        # X/Y/Z in
        ],
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((d_pad, k_pad), jnp.float32)] * 3,
        interpret=interpret,
    )(a, params, psi_p, x_p, y_p, z_p)
    return tuple(o[:d, :k] for o in outs)


@functools.partial(
    jax.jit, static_argnames=("beta", "m", "t_blk", "d_blk"),
)
def psparse_update_ref(a, x_s, y_s, z_s, params, psi, *, beta: float,
                       m: int, t_blk: int = DEFAULT_T_BLK,
                       d_blk: int = DEFAULT_D_BLK):
    """jnp mirror of the kernel — SAME padding, SAME per-t-block tile
    generation and SAME block-sequential accumulation order, so in
    interpret mode the two lower to identical f32 dot/add sequences and
    agree bit for bit (the CPU/differential oracle; asserted by
    tests/test_property.py and the bench). On real Mosaic hardware the
    guarantee weakens to allclose (DESIGN.md §13, CPU-sim caveat)."""
    T, d = a.shape
    k = x_s.shape[1]
    t_blk = min(t_blk, T)
    T_pad = -(-T // t_blk) * t_blk
    k_pad = -(-k // LANE) * LANE
    alpha = psparse_scale(T, m)

    a = jnp.pad(a, ((0, T_pad - T), (0, 0)))
    x_p, y_p, z_p = (jnp.pad(s, ((0, 0), (0, k_pad - k)))
                     for s in (x_s, y_s, z_s))
    psi_p = jnp.pad(psi[None, :], ((0, 0), (0, k_pad - k)))

    # raw per-block dot sums in the kernel's j order, then one
    # fully-barriered finalize — the exact structure of `_kernel`
    accs = None
    for j in range(T_pad // t_blk):
        at = a[j * t_blk:(j + 1) * t_blk].astype(jnp.float32).T
        gen = functools.partial(
            _gen_tile, params, t0=j * t_blk, t_blk=t_blk, k_pad=k_pad,
            m=m, num_tokens=T, alpha=alpha)
        dots = tuple(jax.lax.dot(at, gen(i),
                                 preferred_element_type=jnp.float32)
                     for i in range(3))
        accs = dots if accs is None else \
            tuple(acc + dd for acc, dd in zip(accs, dots))
    scale = 1.0 - beta
    x_acc = _finalize(beta, scale, x_p, accs[0])
    y_acc = _finalize(beta, scale, y_p, accs[1])
    z_acc = _finalize(beta, scale, z_p, accs[2],
                      psi_p.astype(jnp.float32))
    return tuple(o[:, :k] for o in (x_acc, y_acc, z_acc))
