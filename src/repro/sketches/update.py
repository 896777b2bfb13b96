"""THE canonical EMA-triple update (paper Eqs. 5a-5c) — DESIGN.md §6.

Every sketch-state layout in this repo (stacked ``SketchState``, the LM
NodeTree, the MLP paper trainer, the corange variant) funnels through the
two functions here; no other module may inline the EMA recurrence.

``ema_triple_update`` dispatches between

  * the fused Pallas kernel ``kernels/sketch_update`` — one HBM pass over
    the activation matrix for all three contractions (DESIGN.md §7).
    Selected when ``use_kernel`` is True, or by default whenever
    ``kernels.ops.use_pallas(True)`` is active (interpret mode off the
    TPU, Mosaic on it: ``kernels.ops.interpret_mode``);
  * the pure-jnp reference path — bit-identical to the historical
    ``ema_node_update`` / ``sketch_update_single`` implementations, the
    default on CPU where interpret-mode Pallas would dominate runtime.

DP-exact semantics (DESIGN.md §4): with ``axis_name`` set, the per-token
increments are ``psum``-ed across the data-parallel axis BEFORE the EMA
accumulate, so every worker holds the exact full-batch sketch (the
increment is linear in the token rows; summing per-shard partial
contractions is exactly the full-batch contraction). Without it, each
worker sketches only its shard.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.telemetry import scope

Array = jax.Array


# ---------------------------------------------------------------------------
# Masking utilities (static-shape adaptive rank, DESIGN.md §1)
# ---------------------------------------------------------------------------


def active_mask(k_active: Array, k_max: int, dtype=jnp.float32) -> Array:
    """(k_max,) 1.0 for columns < k_active else 0.0."""
    return (jnp.arange(k_max) < k_active).astype(dtype)


def mask_columns(m: Array, k_active) -> Array:
    """Zero the inactive trailing columns of (..., k_max)."""
    return m * active_mask(k_active, m.shape[-1], m.dtype)


# ---------------------------------------------------------------------------
# Row binding (DESIGN.md §15): activations with fewer rows than the
# tree's token binding
# ---------------------------------------------------------------------------


def proj_num_tokens(proj) -> int:
    """The token-row binding T of a projection pytree: the static
    ``num_tokens`` for seeds-only psparse projections, else the leading
    dim of the dense (T, k_max) matrices."""
    from repro.sketches.psparse import PsparseProjections
    if isinstance(proj, PsparseProjections):
        return proj.num_tokens
    return proj["omega"].shape[0]


def pad_activation_rows(a: Array, num_tokens: int) -> Array:
    """Zero-pad a (rows, d) activation to the tree's (T, d) row binding.

    Row-deficient node families (per-expert capacity slots C < T,
    recurrent carries with B rows, the second conv stage) cannot
    prefix-slice the projection instead: psparse hashes bind rows to
    [0, T) statically, so padding the ACTIVATION is the one path that
    is mathematically identical across proj kinds (zero rows contract
    to exact zeros in every increment term)."""
    rows = a.shape[0]
    if rows == num_tokens:
        return a
    if rows > num_tokens:
        raise ValueError(
            f"activation has {rows} rows but the sketch tree is bound "
            f"to num_tokens={num_tokens}; re-init the tree with "
            f"num_tokens >= the largest node's row count")
    return jnp.pad(a, ((0, num_tokens - rows), (0, 0)))


# ---------------------------------------------------------------------------
# The one EMA-triple update
# ---------------------------------------------------------------------------


def ema_triple_update(
    x_s: Array,            # (d, k_max) input/co-range sketch X_s
    y_s: Array,            # (d, k_max) output/range sketch Y_s
    z_s: Array,            # (d, k_max) interaction sketch Z_s
    a: Array,              # (T, d) the node's activation (stop-gradded)
    upsilon: Array,        # (T, k_max)
    omega: Array,          # (T, k_max)
    phi: Array,            # (T, k_max)
    psi: Array,            # (k_max,) node-specific interaction weights
    beta: float,
    k_active,              # () int32 — active k = 2r+1 (traced OK)
    *,
    a_out: Array | None = None,   # legacy layer-indexed form: X observes
    #                               `a` (= A^[l-1]) while Y/Z observe
    #                               a_out (= A^[l]); node-indexed callers
    #                               leave it None (all three observe `a`)
    axis_name: str | tuple[str, ...] | None = None,  # DP-exact: psum
    #                               increments across this mesh axis (a
    #                               tuple psums over the flattened
    #                               multi-axis dp group, e.g. pod+data)
    use_kernel: bool | None = None,  # None -> kernels.ops.pallas_enabled()
) -> tuple[Array, Array, Array]:
    """One EMA sketch update; returns masked (x, y, z) in x_s.dtype."""
    with scope("sketch_update"):
        a = jax.lax.stop_gradient(a)
        dt = x_s.dtype
        ups = mask_columns(upsilon.astype(dt), k_active)
        omg = mask_columns(omega.astype(dt), k_active)
        ph = mask_columns(phi.astype(dt), k_active)
        ps = mask_columns(psi.astype(dt), k_active)

        if use_kernel is None:
            from repro.kernels.ops import pallas_enabled
            use_kernel = pallas_enabled()

        if use_kernel and a_out is None:
            return _fused_kernel_update(
                x_s, y_s, z_s, a, ups, omg, ph, ps, beta, k_active,
                axis_name)

        at = a.astype(dt).T                                    # (d, T)
        aot = at if a_out is None \
            else jax.lax.stop_gradient(a_out).astype(dt).T
        if axis_name is None:
            x_new = beta * x_s + (1.0 - beta) * (at @ ups)
            y_new = beta * y_s + (1.0 - beta) * (aot @ omg)
            z_new = beta * z_s + (1.0 - beta) * (
                (aot @ ph) * ps[None, :])
        else:
            # full-batch increments: sum the per-shard contractions first
            inc_x = jax.lax.psum((1.0 - beta) * (at @ ups), axis_name)
            inc_y = jax.lax.psum((1.0 - beta) * (aot @ omg), axis_name)
            inc_z = jax.lax.psum(
                (1.0 - beta) * ((aot @ ph) * ps[None, :]), axis_name)
            x_new = beta * x_s + inc_x
            y_new = beta * y_s + inc_y
            z_new = beta * z_s + inc_z
        # keep masked columns exactly zero (EMA of zero is zero, but
        # guard against drift after a rank decrease)
        return (
            mask_columns(x_new, k_active),
            mask_columns(y_new, k_active),
            mask_columns(z_new, k_active),
        )


def _fused_kernel_update(x_s, y_s, z_s, a, ups, omg, ph, ps, beta,
                         k_active, axis_name):
    """Route through the fused Pallas kernel (projections pre-masked so
    the kernel's padded columns contribute zeros)."""
    from repro.kernels.sketch_update import sketch_update

    f32 = jnp.float32
    if axis_name is None:
        xn, yn, zn = sketch_update(
            a, x_s.astype(f32), y_s.astype(f32), z_s.astype(f32),
            ups.astype(f32), omg.astype(f32), ph.astype(f32),
            ps.astype(f32), beta=float(beta))
    else:
        # DP-exact: the kernel with zero input sketches yields the pure
        # (1-beta)-scaled increment, which is psum-mergeable
        zeros = jnp.zeros(x_s.shape, f32)
        ix, iy, iz = sketch_update(
            a, zeros, zeros, zeros,
            ups.astype(f32), omg.astype(f32), ph.astype(f32),
            ps.astype(f32), beta=float(beta))
        xn = beta * x_s.astype(f32) + jax.lax.psum(ix, axis_name)
        yn = beta * y_s.astype(f32) + jax.lax.psum(iy, axis_name)
        zn = beta * z_s.astype(f32) + jax.lax.psum(iz, axis_name)
    dt = x_s.dtype
    return (
        mask_columns(xn.astype(dt), k_active),
        mask_columns(yn.astype(dt), k_active),
        mask_columns(zn.astype(dt), k_active),
    )


# ---------------------------------------------------------------------------
# Deferred-collective form (DESIGN.md §9): local increment + merge-apply
# ---------------------------------------------------------------------------


def ema_triple_increment(
    x_s: Array, y_s: Array, z_s: Array,
    a: Array,
    upsilon: Array, omega: Array, phi: Array, psi: Array,
    beta: float,
    k_active,
    *,
    a_out: Array | None = None,
    use_kernel: bool | None = None,
) -> tuple[Array, Array, Array]:
    """The worker-LOCAL masked ``(1-beta)``-scaled increments of one EMA
    update — the quantity the fused DP step packs onto its single
    flat-segment psum instead of psum-ing per node inside the forward.

    Bit-compatibility contract with ``ema_triple_update(axis_name=...)``:
    this computes exactly the expression that path feeds its psum, so

        ema_apply_increment(x, psum(ema_triple_increment(...)), ...)
        == ema_triple_update(..., axis_name=ax)

    element for element (the differential tier in
    tests/test_distributed.py asserts it bitwise at W=4). x_s/y_s/z_s
    contribute only their dtype (projections are cast to it, mirroring
    the inline path).
    """
    a = jax.lax.stop_gradient(a)
    dt = x_s.dtype
    ups = mask_columns(upsilon.astype(dt), k_active)
    omg = mask_columns(omega.astype(dt), k_active)
    ph = mask_columns(phi.astype(dt), k_active)
    ps = mask_columns(psi.astype(dt), k_active)

    if use_kernel is None:
        from repro.kernels.ops import pallas_enabled
        use_kernel = pallas_enabled()

    if use_kernel and a_out is None:
        # the fused kernel with zero input sketches yields the pure
        # (1-beta)-scaled f32 increment — same trick as the DP-exact
        # kernel branch, minus its psum
        from repro.kernels.sketch_update import sketch_update

        f32 = jnp.float32
        zeros = jnp.zeros(x_s.shape, f32)
        return sketch_update(
            a, zeros, zeros, zeros,
            ups.astype(f32), omg.astype(f32), ph.astype(f32),
            ps.astype(f32), beta=float(beta))

    at = a.astype(dt).T                                    # (d, T)
    aot = at if a_out is None \
        else jax.lax.stop_gradient(a_out).astype(dt).T
    inc_x = (1.0 - beta) * (at @ ups)
    inc_y = (1.0 - beta) * (aot @ omg)
    inc_z = (1.0 - beta) * ((aot @ ph) * ps[None, :])
    return inc_x, inc_y, inc_z


def ema_apply_increment(x_s: Array, inc: Array, beta: float,
                        k_active) -> Array:
    """Fold a (merged) increment into the EMA state:
    ``mask(beta * x + inc)`` in the increment's dtype, cast back to the
    sketch dtype — the exact accumulate formula of both the jnp and the
    kernel ``axis_name`` branches above."""
    with scope("sketch_update"):
        xn = beta * x_s.astype(inc.dtype) + inc
        return mask_columns(xn.astype(x_s.dtype), k_active)


# ---------------------------------------------------------------------------
# proj_kind dispatch (DESIGN.md §13): dense Gaussian vs psparse seeds
# ---------------------------------------------------------------------------


def proj_triple_update(
    x_s: Array, y_s: Array, z_s: Array,
    a: Array,
    proj,                  # {"upsilon","omega","phi"} dense dict OR
    #                        a PsparseProjections seeds-only pytree
    psi: Array,
    beta: float,
    k_active,
    *,
    a_out: Array | None = None,
    axis_name: str | tuple[str, ...] | None = None,
    use_kernel: bool | None = None,
) -> tuple[Array, Array, Array]:
    """`ema_triple_update` routed by projection kind. Dense dict trees
    take the canonical path above unchanged; psparse trees regenerate
    the implicit projections on the fly — in-register by the psparse
    Pallas kernel, or as an m-row gather + small contraction on the jnp
    path — and fold increments in through `ema_apply_increment`, so the
    increment/apply bit-compatibility contract holds for psparse BY
    CONSTRUCTION under every DP layout (the update IS apply(psum(inc)))."""
    from repro.sketches.psparse import PsparseProjections

    if not isinstance(proj, PsparseProjections):
        return ema_triple_update(
            x_s, y_s, z_s, a, proj["upsilon"], proj["omega"],
            proj["phi"], psi, beta, k_active, a_out=a_out,
            axis_name=axis_name, use_kernel=use_kernel)

    if use_kernel is None:
        from repro.kernels.ops import pallas_enabled
        use_kernel = pallas_enabled()

    if use_kernel and a_out is None and axis_name is None:
        from repro.kernels.psparse_update import psparse_update

        f32 = jnp.float32
        ps = mask_columns(psi.astype(f32), k_active)
        xn, yn, zn = psparse_update(
            jax.lax.stop_gradient(a), x_s.astype(f32), y_s.astype(f32),
            z_s.astype(f32), proj.params, ps, beta=float(beta),
            m=proj.m)
        dt = x_s.dtype
        return tuple(mask_columns(o.astype(dt), k_active)
                     for o in (xn, yn, zn))

    inc_x, inc_y, inc_z = proj_triple_increment(
        x_s, y_s, z_s, a, proj, psi, beta, k_active, a_out=a_out,
        use_kernel=use_kernel)
    if axis_name is not None:
        inc_x = jax.lax.psum(inc_x, axis_name)
        inc_y = jax.lax.psum(inc_y, axis_name)
        inc_z = jax.lax.psum(inc_z, axis_name)
    return (
        ema_apply_increment(x_s, inc_x, beta, k_active),
        ema_apply_increment(y_s, inc_y, beta, k_active),
        ema_apply_increment(z_s, inc_z, beta, k_active),
    )


def proj_triple_increment(
    x_s: Array, y_s: Array, z_s: Array,
    a: Array,
    proj,
    psi: Array,
    beta: float,
    k_active,
    *,
    a_out: Array | None = None,
    use_kernel: bool | None = None,
) -> tuple[Array, Array, Array]:
    """`ema_triple_increment` routed by projection kind — increments
    keep their (d, k_max) shapes regardless of kind, so the flat-segment
    wire packing and every DP merge layout work unchanged."""
    from repro.sketches.psparse import PsparseProjections

    if not isinstance(proj, PsparseProjections):
        return ema_triple_increment(
            x_s, y_s, z_s, a, proj["upsilon"], proj["omega"],
            proj["phi"], psi, beta, k_active, a_out=a_out,
            use_kernel=use_kernel)
    if a_out is not None:
        raise NotImplementedError(
            "psparse projections have no legacy a_out form — "
            "node-indexed callers observe a single activation")

    if use_kernel is None:
        from repro.kernels.ops import pallas_enabled
        use_kernel = pallas_enabled()

    if use_kernel:
        # zero input sketches -> the pure (1-beta)-scaled increment,
        # same trick as the dense kernel branch
        from repro.kernels.psparse_update import psparse_update

        f32 = jnp.float32
        ps = mask_columns(psi.astype(f32), k_active)
        zeros = jnp.zeros(x_s.shape, f32)
        ix, iy, iz = psparse_update(
            jax.lax.stop_gradient(a), zeros, zeros, zeros, proj.params,
            ps, beta=float(beta), m=proj.m)
    else:
        from repro.kernels.psparse_update import psparse_triple_increment

        dt = x_s.dtype
        ps = mask_columns(psi.astype(dt), k_active)
        ix, iy, iz = psparse_triple_increment(
            a, proj.params, ps, float(beta), proj.m, dtype=dt)
    # column masking: inc_z is masked through psi; x/y explicitly (a
    # masked projection column IS a masked increment column — the
    # contraction is per-column, and 0-columns contract to exact zeros)
    return mask_columns(ix, k_active), mask_columns(iy, k_active), iz


# ---------------------------------------------------------------------------
# Corange (Tropp) triple — the other sketch kind a node may carry
# ---------------------------------------------------------------------------


def corange_triple_increment(
    x_c: Array, y_c: Array, z_c: Array,
    a: Array,
    proj,
    beta: float,
    k_active,
) -> tuple[Array, Array, Array]:
    """Worker-LOCAL masked ``(1-beta)``-scaled increments of one corange
    EMA update — every term of the Tropp triple is LINEAR in the batch
    matrix ``M = a^T``, so the zero-state update IS the psum-mergeable
    increment (the corange analogue of ``ema_triple_increment``).
    x_c/y_c/z_c contribute only their shapes and dtypes."""
    return corange_triple_update(
        jnp.zeros_like(x_c), jnp.zeros_like(y_c), jnp.zeros_like(z_c),
        a, proj, beta, k_active)


def corange_apply_increment(
    x_c: Array, y_c: Array, z_c: Array,
    inc_x: Array, inc_y: Array, inc_z: Array,
    beta: float,
    k_active,
) -> tuple[Array, Array, Array]:
    """Fold (merged) corange increments into the EMA triple with the
    exact masking of ``corange_triple_update`` (x masked along its
    leading k axis, y along its trailing k axis, z on both dims at
    s_active = 2k+1) — bitwise the accumulate that path computes, since
    the increments arrive already masked and masking is idempotent."""
    s_active = 2 * k_active + 1
    x_new = mask_columns((beta * x_c + inc_x).T, k_active).T
    y_new = mask_columns(beta * y_c + inc_y, k_active)
    z_new = beta * z_c + inc_z
    z_new = mask_columns(mask_columns(z_new, s_active).T, s_active).T
    return x_new, y_new, z_new


def corange_triple_update(
    x_c: Array,        # (k_max, N_b) co-range sketch
    y_c: Array,        # (d, k_max)   range sketch
    z_c: Array,        # (s_max, s_max) core sketch, s = 2k+1
    a: Array,          # (N_b, d) current batch activations
    proj,              # CorangeProjections (duck-typed)
    beta: float,
    k_active,
) -> tuple[Array, Array, Array]:
    """EMA update of the Tropp triple against M_batch = a^T (DESIGN.md §1)."""
    a = jax.lax.stop_gradient(a)
    dt = x_c.dtype
    s_active = 2 * k_active + 1
    m = a.astype(dt).T                                     # (d, N_b)
    ups = mask_columns(proj.upsilon.astype(dt).T, k_active).T   # mask rows
    omg = mask_columns(proj.omega.astype(dt), k_active)
    phi = mask_columns(proj.phi.astype(dt).T, s_active).T
    psi = mask_columns(proj.psi.astype(dt), s_active)
    x_new = beta * x_c + (1 - beta) * (ups @ m)
    y_new = beta * y_c + (1 - beta) * (m @ omg)
    z_new = beta * z_c + (1 - beta) * (phi @ (m @ psi))
    x_new = mask_columns(x_new.T, k_active).T
    y_new = mask_columns(y_new, k_active)
    z_new = mask_columns(mask_columns(z_new, s_active).T, s_active).T
    return x_new, y_new, z_new
