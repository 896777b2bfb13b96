"""attention_roofline: the model's attention (`chunked_causal_attention`,
forward and backward) run alone at the cell's shapes: the least time
the chip could take for the causal attention's operations and bytes
(`flops.attention`) over its device time."""
import jax
import jax.numpy as jnp

from bench import flops, peaks
from repro.models.attention import chunked_causal_attention


def bench_attention(q, k, v, do):
    out, vjp = jax.vjp(
        lambda q, k, v: chunked_causal_attention(q, k, v, window=None),
        q, k, v)
    return out, vjp(do)


def read(ctx):
    a = ctx.arch
    B, S = ctx.cell.traffic["batch"], ctx.cell.traffic["seq_len"]
    KV, D = a.num_kv_heads, a.resolved_head_dim
    G = a.num_heads // KV
    ks = jax.random.split(jax.random.PRNGKey(ctx.seed), 4)
    q = jax.random.normal(ks[0], (B, S, KV, G, D), a.dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), a.dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), a.dtype)
    do = jax.random.normal(ks[3], (B, S, KV, G, D), a.dtype)
    secs = ctx.seconds_alone("bench_attention", jax.jit(bench_attention),
                             (q, k, v, do))
    if not secs:
        return None
    need = flops.attention(B, S, a.num_heads, KV, D,
                           act_bytes=jnp.dtype(a.dtype).itemsize)
    return 100.0 * peaks.least_seconds(need["flops"], need["bytes"],
                                       ctx.device_kind) / secs
