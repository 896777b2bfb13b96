"""GQA attention: chunked-causal (train/prefill) + KV-cache decode.

Train/prefill uses an online-softmax scan over KV chunks (flash-attention
schedule expressed in XLA; the Pallas TPU kernel in kernels/flash_attention
implements the same tiling for the hot path). Decode supports full caches
and ring-buffer windowed caches (SWA/local/global-fallback); when
kv_heads < TP the cache is sequence-sharded over the model axis and XLA
merges partial softmaxes (flash-decoding; explicit collective in
parallel/collectives.merge_partial_attn).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, rope
from repro.parallel.sharding import constrain
from repro.telemetry import scope

Array = jax.Array
NEG_INF = -1e30


def attn_init(key, cfg, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, cfg.num_heads, hd), dtype, fan_in=d),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads, hd), dtype, fan_in=d),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads, hd), dtype, fan_in=d),
        "wo": dense_init(
            ks[3], (cfg.num_heads, hd, d), dtype, fan_in=cfg.num_heads * hd
        ),
    }


def resolve_window(cfg, layer_type: str, seq_len: int) -> int | None:
    """Effective attention window for a layer type at a given seq_len."""
    if layer_type in ("swa", "local"):
        return cfg.window_size
    if layer_type == "global" and seq_len >= 262_144:
        # long-context fallback for global layers (DESIGN.md §8)
        return 8_192
    return None  # full attention


def cache_capacity(cfg, layer_type: str, seq_len: int) -> int:
    w = resolve_window(cfg, layer_type, seq_len)
    return min(seq_len, w) if w else seq_len


# ---------------------------------------------------------------------------
# chunked causal attention (train / prefill)
# ---------------------------------------------------------------------------


def chunked_causal_attention(
    q: Array,              # (B, S, KV, G, D)  grouped query heads
    k: Array,              # (B, S, KV, D)
    v: Array,              # (B, S, KV, D)
    *,
    window: int | None,
    chunk: int = 1024,
) -> Array:
    with scope("attention"):
        B, S, KV, G, D = q.shape
        chunk = min(chunk, S)
        assert S % chunk == 0, (S, chunk)
        n_chunks = S // chunk
        scale = D ** -0.5
        qf = (q * scale).astype(q.dtype)
        q_pos = jnp.arange(S)

        m0 = jnp.full((B, KV, G, S), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, S), jnp.float32)
        a0 = jnp.zeros((B, KV, G, S, D), jnp.float32)

        def body(carry, j):
            m, l, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * chunk, chunk,
                                              axis=1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * chunk, chunk,
                                              axis=1)
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qf, kj,
                preferred_element_type=jnp.float32
            )
            # additive 2D mask (broadcast at the add): a boolean 5D mask
            # gets hoisted/stacked by XLA's loop optimizer into a
            # (n_chunks, B, ...) pred carry — hundreds of MB per layer.
            # Keep it (S, chunk) f32.
            k_pos = j * chunk + jnp.arange(chunk)
            bias = jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0,
                             NEG_INF)
            if window is not None:
                bias = bias + jnp.where(
                    (q_pos[:, None] - k_pos[None, :]) < window, 0.0, NEG_INF)
            s = s + bias[None, None, None]
            m_new = jnp.maximum(m, s.max(axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(v.dtype), vj,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        # checkpoint the chunk body: the scan's VJP otherwise saves the
        # (B,KV,G,S,chunk) softmax intermediates for every chunk —
        # recomputing them in the backward sweep is the flash-attention
        # trade.
        body = jax.checkpoint(body, prevent_cse=False)
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0), jnp.arange(n_chunks)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (B,KV,G,S,D) -> (B,S,KV,G,D)
        return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode attention over a (possibly ring) cache
# ---------------------------------------------------------------------------


def decode_attention(
    q: Array,               # (B, 1, KV, G, D)
    cache_k: Array,         # (B, KV, C, D)
    cache_v: Array,         # (B, KV, C, D)
    positions: Array,       # (B,) current absolute position
    *,
    window: int | None,
    ring: bool,
) -> Array:
    B, _, KV, G, D = q.shape
    C = cache_k.shape[2]
    scale = D ** -0.5
    s = jnp.einsum(
        "bqhgd,bhcd->bhgqc", q * scale, cache_k,
        preferred_element_type=jnp.float32,
    )  # (B, KV, G, 1, C)
    idx = jnp.arange(C)
    pos = positions[:, None]                       # (B, 1)
    if ring:
        # slot i holds absolute position  pos - ((pos - i) mod C)
        abs_pos = pos - jnp.mod(pos - idx[None, :], C)
    else:
        abs_pos = jnp.broadcast_to(idx[None, :], (B, C))
    valid = (abs_pos >= 0) & (abs_pos <= pos)
    if window is not None:
        valid &= abs_pos > (pos - window)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhgqc,bhcd->bqhgd", p.astype(cache_v.dtype), cache_v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# full attention layer (projections + rope + cache handling)
# ---------------------------------------------------------------------------


def attn_apply(
    p: dict,
    x: Array,                       # (B, S, d)
    *,
    cfg,
    layer_type: str,
    positions: Array,               # (B, S) train/prefill; (B,) decode
    mode: str,                      # train | eval | prefill | decode
    cache: dict | None = None,      # decode/prefill cache in/out
    seq_len_ctx: int,               # context length the cache is sized for
    chunk: int = 1024,
) -> tuple[Array, dict | None]:
    B, S, d = x.shape
    KV, Hq, D = cfg.num_kv_heads, cfg.num_heads, cfg.resolved_head_dim
    G = Hq // KV
    dt = x.dtype
    window = resolve_window(cfg, layer_type, seq_len_ctx)
    cap = cache_capacity(cfg, layer_type, seq_len_ctx)
    ring = cap < seq_len_ctx

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    q = constrain(q, "batch", "seq_attn", "heads_act", "none")

    pos2d = positions if positions.ndim == 2 else positions[:, None]
    q = rope(q, pos2d, cfg.rope_theta)
    k = rope(k, pos2d, cfg.rope_theta)
    # kv heads are few: keep K/V seq-replicated so the chunked-attention
    # dynamic slice never crosses a seq-sharded layout (avoids SPMD
    # involuntary remat; q carries the heads-TP sharding).
    k = constrain(k, "batch", "none", "none", "none")
    v = constrain(v, "batch", "none", "none", "none")
    qg = q.reshape(B, S, KV, G, D)

    new_cache = None
    if mode in ("train", "eval", "prefill"):
        out = chunked_causal_attention(qg, k, v, window=window, chunk=chunk)
        if mode == "prefill":
            kc = k.transpose(0, 2, 1, 3)       # (B, KV, S, D)
            vc = v.transpose(0, 2, 1, 3)
            if cap < S:
                kc, vc = kc[:, :, S - cap:], vc[:, :, S - cap:]
                # place abs position p at slot p % cap
                perm = jnp.mod(jnp.arange(S - cap, S), cap)
                inv = jnp.argsort(perm)
                kc, vc = kc[:, :, inv], vc[:, :, inv]
            elif cap > S:
                pad = ((0, 0), (0, 0), (0, cap - S), (0, 0))
                kc, vc = jnp.pad(kc, pad), jnp.pad(vc, pad)
            new_cache = _constrain_cache(
                {"k": kc.astype(dt), "v": vc.astype(dt)}, cfg
            )
    else:  # decode: S == 1
        assert cache is not None
        slot = jnp.mod(positions, cap) if ring else positions  # (B,)
        b_idx = jnp.arange(B)
        ck = cache["k"].at[b_idx, :, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[b_idx, :, slot].set(v[:, 0].astype(cache["v"].dtype))
        ck = _constrain_cache({"k": ck, "v": cv}, cfg)
        out = decode_attention(
            qg, ck["k"], ck["v"], positions, window=window, ring=ring
        )
        new_cache = ck

    out = out.reshape(B, S, Hq, D)
    out = constrain(out, "batch", "seq_attn", "heads_act", "none")
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return y, new_cache


def _constrain_cache(cache: dict, cfg) -> dict:
    """Cache layout: kv-head sharded when KV >= TP else sequence-sharded."""
    from repro.parallel.sharding import current_rules

    rules = current_rules()
    tp = rules.tp_size if rules is not None else 1

    def c(t):
        if cfg.num_kv_heads >= tp:
            return constrain(t, "batch", "heads_act", "none", "none")
        return constrain(t, "batch", "none", "kvseq", "none")
    return {k: c(v) for k, v in cache.items()}


def init_attn_cache(cfg, layer_type: str, batch: int, seq_len_ctx: int,
                    dtype) -> dict:
    cap = cache_capacity(cfg, layer_type, seq_len_ctx)
    KV, D = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (batch, KV, cap, D)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
