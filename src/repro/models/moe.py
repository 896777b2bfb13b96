"""Mixture-of-Experts FFN: top-k routing, capacity dispatch, EP/TP hybrid.

Dispatch is sort-based (argsort by expert id -> position-in-expert ->
slot gather), the approach that scales to fine-grained MoE (128 experts)
where one-hot dispatch einsums are infeasible. Distribution (DESIGN.md §4):

  EP mode (E % tp == 0, e.g. qwen3 128e/16):  experts sharded over the
      model axis; activations replicated over model inside the block; each
      shard gathers only its local experts' slots; combine = psum(model).
  TP mode (E < tp, e.g. mixtral 8e/16): every shard holds all experts with
      d_ff/tp columns (Megatron column+row pair per expert); combine =
      psum(model).

Without active sharding rules the same math runs as a single-device
reference path (used by smoke tests and the oracle comparison against
`moe_dense_ref`).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init
from repro.parallel.sharding import current_rules

Array = jax.Array


def moe_init(key, cfg, dtype):
    E, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, E), dtype),
        "we_gate": dense_init(ks[1], (E, d, f), dtype, fan_in=d),
        "we_up": dense_init(ks[2], (E, d, f), dtype, fan_in=d),
        "we_down": dense_init(ks[3], (E, f, d), dtype, fan_in=f),
    }


def capacity(tokens_local: int, num_experts: int, k: int,
             capacity_factor: float) -> int:
    c = math.ceil(tokens_local * k * capacity_factor / num_experts)
    return max(4, -(-c // 4) * 4)          # multiple of 4, >= 4


# ---------------------------------------------------------------------------
# local (per-shard) routing + dispatch metadata
# ---------------------------------------------------------------------------


def route(x: Array, router_w: Array, k: int):
    """Returns (probs (T,E) f32, topw (T,k), tope (T,k) int32)."""
    logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, tope = jax.lax.top_k(probs, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    return probs, topw, tope.astype(jnp.int32)


def dispatch_meta(tope: Array, topw: Array, E: int, C: int):
    """Sort-based slot assignment.

    Returns tok (E*C,) source-token index per slot, wgt (E*C,) combine
    weight, valid (E*C,) bool. Tokens beyond capacity are dropped
    (drop-late: stable argsort keeps earlier tokens).
    """
    T, K = tope.shape
    flat_e = tope.reshape(-1)
    flat_t = jnp.arange(T * K, dtype=jnp.int32) // K
    flat_w = topw.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * K, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = pos < C
    slot = jnp.where(keep, se * C + pos, E * C)        # E*C = dropped bin
    tok = jnp.zeros((E * C,), jnp.int32).at[slot].set(
        flat_t[order], mode="drop")
    wgt = jnp.zeros((E * C,), flat_w.dtype).at[slot].set(
        flat_w[order], mode="drop")
    valid = jnp.zeros((E * C,), jnp.bool_).at[slot].set(
        True, mode="drop")
    return tok, wgt, valid


def _expert_ffn(xg: Array, wg: Array, wu: Array, wd: Array) -> Array:
    """xg (E', C, d) -> (E', C, d) through swiglu expert FFNs."""
    dt = xg.dtype
    g = jnp.einsum("ecd,edf->ecf", xg, wg.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xg, wu.astype(dt))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    return jnp.einsum("ecf,efd->ecd", h, wd.astype(dt))


def aux_load_balance(probs: Array, tope: Array, E: int) -> Array:
    """Switch/GShard load-balance loss: E * sum(frac_routed * mean_prob)."""
    T, K = tope.shape
    me = probs.mean(axis=0)                                   # (E,)
    ce = jnp.bincount(tope.reshape(-1), length=E) / (T * K)
    return E * jnp.sum(me * ce)


# ---------------------------------------------------------------------------
# single-device reference path
# ---------------------------------------------------------------------------


def moe_apply_ref(p: dict, x: Array, cfg, *, return_dispatch=False):
    """x (T, d) -> (y (T, d), aux ()) without collectives.

    With ``return_dispatch`` also returns the valid-masked dispatched
    per-expert input xg (E, C, d) — the activation the per-expert
    sketch nodes observe (DESIGN.md §15). Dropped/empty slots are exact
    zero rows, which contract to zero in every sketch increment term.
    """
    T, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    C = capacity(T, E, K, cfg.capacity_factor)
    probs, topw, tope = route(x, p["router"], K)
    tok, wgt, valid = dispatch_meta(tope, topw, E, C)
    xg = (x[tok] * valid[:, None].astype(x.dtype)).reshape(E, C, d)
    out = _expert_ffn(
        xg, p["we_gate"], p["we_up"], p["we_down"]
    ).reshape(E * C, d)
    w = (wgt * valid).astype(x.dtype)[:, None]
    y = jnp.zeros_like(x).at[tok].add(out * w, mode="drop")
    aux = aux_load_balance(probs, tope, E)
    if return_dispatch:
        return y, aux, xg
    return y, aux


def moe_dense_ref(p: dict, x: Array, cfg) -> Array:
    """Oracle: every expert on every token, combined by full top-k weights
    (no capacity drops). Tests compare moe_apply_ref against this."""
    E, K = cfg.num_experts, cfg.experts_per_token
    probs, topw, tope = route(x, p["router"], K)
    cw = jnp.zeros_like(probs)
    for j in range(K):
        cw = cw.at[jnp.arange(x.shape[0]), tope[:, j]].add(topw[:, j])
    outs = _expert_ffn(
        jnp.broadcast_to(x, (E,) + x.shape),
        p["we_gate"], p["we_up"], p["we_down"],
    )                                                # (E, T, d)
    return jnp.einsum("etd,te->td", outs, cw.astype(x.dtype))


# ---------------------------------------------------------------------------
# distributed path (shard_map)
# ---------------------------------------------------------------------------


def moe_apply(p: dict, x: Array, cfg, *, return_dispatch=False):
    """x (B, S, d) -> (y (B, S, d), aux ()). Dispatches on active rules.

    With ``return_dispatch`` a third output carries the per-expert
    dispatched input for the sketch nodes, normalized to (E, rows, d):
    rows = C on the reference path; on the shard_map path rows =
    dp_size * C (every dp shard's capacity slab, expert dim sharded
    over the model axis so each EP shard holds only its local experts).
    The sketch increment is linear in rows, so sketching the
    concatenated slabs equals summing per-shard increments.
    """
    B, S, d = x.shape
    rules = current_rules()
    if rules is None or (B * S) % rules.dp_size != 0:
        # no rules, or too few tokens to shard over dp (e.g. batch-1
        # long-context decode): the tensors are tiny — run the reference
        # dispatch and let XLA place it.
        out = moe_apply_ref(p, x.reshape(B * S, d), cfg,
                            return_dispatch=return_dispatch)
        if return_dispatch:
            y, aux, xg = out
            return y.reshape(B, S, d), aux, xg
        y, aux = out
        return y.reshape(B, S, d), aux

    E, K = cfg.num_experts, cfg.experts_per_token
    tp = rules.tp_size
    ep_mode = E % tp == 0 and E >= tp
    T = B * S
    T_loc = T // rules.dp_size
    C = capacity(T_loc, E, K, cfg.capacity_factor)
    dp, model = rules.dp, rules.tp_axis

    if ep_mode:
        w_specs = (P(), P(model, None, None), P(model, None, None),
                   P(model, None, None))
    else:
        w_specs = (P(), P(None, None, model), P(None, None, model),
                   P(None, model, None))

    from jax import shard_map

    def _local(xl, router, wg, wu, wd):
        # xl (T_loc, d) — sharded over dp, replicated over model
        probs, topw, tope = route(xl, router, K)
        tok, wgt, valid = dispatch_meta(tope, topw, E, C)
        if ep_mode:
            e_loc = E // tp
            m = jax.lax.axis_index(model)
            sl = lambda a: jax.lax.dynamic_slice_in_dim(
                a.reshape(E, C), m * e_loc, e_loc, axis=0).reshape(-1)
            tok_l, wgt_l, valid_l = sl(tok), sl(wgt), sl(valid)
            n_e = e_loc
        else:
            tok_l, wgt_l, valid_l = tok, wgt, valid
            n_e = E
        xg = (xl[tok_l] * valid_l[:, None].astype(xl.dtype)
              ).reshape(n_e, C, d)
        out = _expert_ffn(xg, wg, wu, wd).reshape(n_e * C, d)
        w = (wgt_l * valid_l).astype(xl.dtype)[:, None]
        part = jnp.zeros_like(xl).at[tok_l].add(out * w, mode="drop")
        y = jax.lax.psum(part, model)
        aux = aux_load_balance(probs, tope, E)
        aux = jax.lax.pmean(aux, rules.dp_axes + (model,))
        if return_dispatch:
            # leading length-1 dim expands over dp: every dp shard
            # contributes its own capacity slab
            return y, aux, xg[None]
        return y, aux

    out_specs = (P(dp, None), P())
    if return_dispatch:
        # expert dim sharded over the model axis in EP mode — each EP
        # shard materializes only its local experts' dispatch slab,
        # exactly like its expert weights
        out_specs += (P(dp, model, None, None) if ep_mode
                      else P(dp, None, None, None),)
    fn = partial(
        shard_map, mesh=rules.mesh,
        in_specs=(P(dp, None),) + w_specs,
        out_specs=out_specs, check_vma=False,
    )(_local)
    out = fn(
        x.reshape(T, d), p["router"], p["we_gate"], p["we_up"], p["we_down"]
    )
    if return_dispatch:
        y, aux, xg = out
        # (dp_size, E, C, d) -> (E, dp_size*C, d): per-expert rows are
        # the concatenation of every dp shard's slots (increment-linear)
        xg = jnp.transpose(xg, (1, 0, 2, 3)).reshape(E, -1, d)
        return y.reshape(B, S, d), aux, xg
    y, aux = out
    return y.reshape(B, S, d), aux
