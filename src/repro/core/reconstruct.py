"""Activation reconstruction from EMA sketches (paper §4.2, Eqs. 6-7).

Two-stage least-squares:
    Y_s = Q_Y R_Y ;  X_s = Q_X R_X            (QR, d x k)
    C_inter = argmin ||Q_Y C - Z_s||_F        (= Q_Y^T Z_s, Q_Y orthonormal)
    X_s^T   = P_X R'_X                        (QR, k x k)
    C       = argmin ||P_X C - C_inter^T||_F  (= P_X^T C_inter^T)
    G~      = Q_Y C Q_X^T                     (d x d feature structure)
    A~      = Omega Y_s^dagger G~             (N_b x d batch projection)

Beyond-paper optimization (DESIGN.md §7): A~ is rank-k by construction, so
we keep it FACTORED as A~ = left @ right^T with left = Omega (Y^+ Q_Y C)
(N_b x k) and right = Q_X (d x k) — no d x d intermediate is ever formed
and the gradient matmul in sketched_linear.py runs at O(k/d) of the dense
FLOPs. `Reconstruction.dense()` materializes A~ for the faithful path and
for tests.

All operations are masked-rank aware: columns >= k_active are exactly
zero throughout, so a runtime rank change never recompiles (static k_max).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.sketches.update import mask_columns
from repro.telemetry import scope

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Reconstruction:
    """A~ ≈ left @ right.T   with left (N_b, k), right (d, k)."""

    left: Array
    right: Array

    def dense(self) -> Array:
        return self.left @ self.right.T


def masked_qr(a: Array, k_active) -> Array:
    """QR of a column-masked matrix; Q columns >= k_active are zeroed so
    junk Householder directions never contaminate downstream products."""
    q, _ = jnp.linalg.qr(a)
    return mask_columns(q, k_active)


def _pinv_apply(y_s: Array, rhs: Array, k_active, mode: str, ridge: float):
    """Y^dagger @ rhs, either via SVD pinv (faithful) or ridge-regularized
    normal equations (fast, TPU-friendly k x k solve).

    The ridge is RELATIVE (scaled by trace(Y^T Y)/k): with an absolute
    ridge, rank-deficient sketches (masked rank, low-rank activations)
    amplify null-space rounding noise by 1/ridge.
    """
    if mode == "faithful":
        return jnp.linalg.pinv(y_s) @ rhs
    g = y_s.T @ y_s                              # (k, k)
    k = g.shape[0]
    lam = ridge * (jnp.trace(g) / k + 1e-30)
    eye = jnp.eye(k, dtype=g.dtype)
    return jnp.linalg.solve(g + lam * eye, y_s.T @ rhs)


def reconstruct(
    x_s: Array,            # (d, k_max) input-pattern sketch of the node
    y_s: Array,            # (d, k_max) output-pattern sketch of the node
    z_s: Array,            # (d, k_max) interaction sketch (s = k)
    omega: Array,          # (N_b, k_max) batch output projection
    k_active,              # traced or static active k
    *,
    mode: str = "faithful",
    ridge: float = 1e-4,
) -> Reconstruction:
    """Reconstruct the node's batch activation matrix from its EMA triple."""
    with scope("reconstruct"):
        dt = jnp.promote_types(x_s.dtype, jnp.float32)
        x_s = mask_columns(x_s.astype(dt), k_active)
        y_s = mask_columns(y_s.astype(dt), k_active)
        z_s = mask_columns(z_s.astype(dt), k_active)
        omega = mask_columns(omega.astype(dt), k_active)

        q_y = masked_qr(y_s, k_active)               # (d, k)
        c_inter = q_y.T @ z_s                        # (k, s)
        p_x = masked_qr(x_s.T, k_active)             # (k, k)
        c = p_x.T @ c_inter.T                        # (k, k)  [s = k]
        q_x = masked_qr(x_s, k_active)               # (d, k)

        # left = Omega @ (Y^+ Q_Y) @ C   — all k-sized
        ypq = _pinv_apply(y_s, q_y, k_active, mode, ridge)   # (k, k)
        left = omega @ (ypq @ c)                     # (N_b, k)
        return Reconstruction(left=left, right=q_x)


def reconstruct_dense_faithful(x_s, y_s, z_s, omega, k_active,
                               *, mode="faithful", ridge=1e-6) -> Array:
    """Literal paper path: materialize G~ (d x d) then project (Eq. 7).

    Used by tests to confirm the factored path is numerically identical.
    """
    dt = jnp.promote_types(x_s.dtype, jnp.float32)
    x_s = mask_columns(x_s.astype(dt), k_active)
    y_s = mask_columns(y_s.astype(dt), k_active)
    z_s = mask_columns(z_s.astype(dt), k_active)
    omega = mask_columns(omega.astype(dt), k_active)
    q_y = masked_qr(y_s, k_active)
    c_inter = q_y.T @ z_s
    p_x = masked_qr(x_s.T, k_active)
    c = p_x.T @ c_inter.T
    q_x = masked_qr(x_s, k_active)
    g = q_y @ c @ q_x.T                          # (d, d) feature structure
    return omega @ _pinv_apply(y_s, g, k_active, mode, ridge)
