"""AdamW + SGD from scratch (pytree optimizers, pjit-friendly: optimizer
state inherits parameter sharding leaf-for-leaf, giving ZeRO-style
sharded moments for free under the param logical-axis rules)."""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.telemetry import scope


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 1.0           # global-norm clip; 0 disables
    moment_dtype: Any = jnp.float32


def init_adamw(params, cfg: AdamWConfig):
    zeros = jax.tree.map(
        lambda p: jnp.zeros(p.shape, cfg.moment_dtype), params)
    return {"m": zeros, "v": jax.tree.map(jnp.zeros_like, zeros),
            "count": jnp.zeros((), jnp.int32)}


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float):
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), gn


def adamw_update(params, grads, state, cfg: AdamWConfig, lr_scale=1.0):
    """Returns (new_params, new_state, metrics)."""
    with scope("adamw"):
        if cfg.grad_clip > 0:
            grads, gn = clip_by_global_norm(grads, cfg.grad_clip)
        else:
            gn = global_norm(grads)
        count = state["count"] + 1
        b1c = 1.0 - cfg.b1 ** count.astype(jnp.float32)
        b2c = 1.0 - cfg.b2 ** count.astype(jnp.float32)
        lr = cfg.lr * lr_scale

        def upd(p, g, m, v):
            gf = g.astype(cfg.moment_dtype)
            m_new = cfg.b1 * m + (1 - cfg.b1) * gf
            v_new = cfg.b2 * v + (1 - cfg.b2) * gf * gf
            step = (m_new / b1c) / (jnp.sqrt(v_new / b2c) + cfg.eps)
            p_new = p.astype(jnp.float32) - lr * (
                step + cfg.weight_decay * p.astype(jnp.float32))
            return p_new.astype(p.dtype), m_new, v_new

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state["m"])
        flat_v = treedef.flatten_up_to(state["v"])
        out = [upd(p, g, m, v) for p, g, m, v
               in zip(flat_p, flat_g, flat_m, flat_v)]
        new_params = treedef.unflatten([o[0] for o in out])
        new_state = {
            "m": treedef.unflatten([o[1] for o in out]),
            "v": treedef.unflatten([o[2] for o in out]),
            "count": count,
        }
        return new_params, new_state, {"grad_norm": gn}


def adamw_sparse_update(params, state, cfg: AdamWConfig, lr_scale=1.0,
                        *, update, idx, unravel):
    """AdamW for a k-SPARSE flat gradient (the SketchedSGD transmitted
    update), decomposed so the collective that produces `update`'s
    values can hide behind the optimizer itself (DESIGN.md §14):

      1. a DENSE pass with zero gradients — it touches only
         params/moments, so it carries NO data dependency on the p2
         all-reduce and XLA is free to run it while the collective is
         in flight;
      2. an exact k-coordinate correction — the `adamw_update` formulas
         recomputed from the PRE-update state at the touched
         coordinates, scattered over the zero-grad result.

    Zero gradients leave the update formula identical at every
    untouched coordinate (m' = b1*m, v' = b2*v, and the clip scale
    multiplies a zero), so the result is BITWISE `adamw_update(params,
    unravel(update), ...)` under jit (the differential tier asserts
    it; like the ring oracle, both sides must be jitted or XLA's
    FMA contraction on the eager side breaks bit-equality).

    `update` is the (D,) flat sparse gradient, `idx` its (k,) nonzero
    coordinate set (distinct), `unravel` the flat->pytree map used by
    the serial path — needed so `global_norm` reduces leaf-by-leaf in
    the serial order. Returns (new_params, new_state, metrics)."""
    with scope("adamw"):
        from jax.flatten_util import ravel_pytree

        zeros = jax.tree.map(jnp.zeros_like, params)
        p0, s0, _ = adamw_update(params, zeros, state, cfg, lr_scale)

        gtree = unravel(update)
        gn = global_norm(gtree)
        if cfg.grad_clip > 0:
            scale = jnp.minimum(1.0,
                                cfg.grad_clip / jnp.maximum(gn, 1e-12))
        else:
            scale = jnp.float32(1.0)

        count = state["count"] + 1
        b1c = 1.0 - cfg.b1 ** count.astype(jnp.float32)
        b2c = 1.0 - cfg.b2 ** count.astype(jnp.float32)
        lr = cfg.lr * lr_scale

        pf, unrav_p = ravel_pytree(params)
        mf, _ = ravel_pytree(state["m"])
        vf, _ = ravel_pytree(state["v"])
        gf = (update[idx] * scale).astype(cfg.moment_dtype)
        m_new = cfg.b1 * mf[idx] + (1 - cfg.b1) * gf
        v_new = cfg.b2 * vf[idx] + (1 - cfg.b2) * gf * gf
        step = (m_new / b1c) / (jnp.sqrt(v_new / b2c) + cfg.eps)
        p_new = pf[idx] - lr * (step + cfg.weight_decay * pf[idx])

        p0f, _ = ravel_pytree(p0)
        m0f, _ = ravel_pytree(s0["m"])
        v0f, _ = ravel_pytree(s0["v"])
        new_params = unrav_p(p0f.at[idx].set(p_new))
        new_state = {"m": unrav_p(m0f.at[idx].set(m_new)),
                     "v": unrav_p(v0f.at[idx].set(v_new)),
                     "count": s0["count"]}
        return new_params, new_state, {"grad_norm": gn}


# --- plain SGD (paper §5.3 problematic config uses SGD) -------------------


def sgd_update(params, grads, lr: float):
    return jax.tree.map(
        lambda p, g: (p.astype(jnp.float32)
                      - lr * g.astype(jnp.float32)).astype(p.dtype),
        params, grads)
