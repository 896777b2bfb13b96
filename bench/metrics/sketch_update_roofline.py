"""sketch_update_roofline: `ema_triple_update` run alone at every
sketched node's shape of one layer (the MLP input, d_model wide, and its
hidden activation, d_ff wide, over the step's T tokens), with the step's
own kernel choice: the least time for their operations and bytes
(`flops.sketch_update`) over their device time."""
import jax
import jax.numpy as jnp

from bench import flops, peaks
from repro.sketches import ema_triple_update


def read(ctx):
    t = ctx.cell.traffic
    if not t["sketch"]:
        return None
    beta = t["beta"]

    def bench_sketch_update(nodes, proj, k_active):
        return [ema_triple_update(x, y, z, a, *proj, psi, beta, k_active)
                for x, y, z, a, psi in nodes]

    a = ctx.arch
    T, k = t["batch"] * t["seq_len"], t["k_max"]
    ks = jax.random.split(jax.random.PRNGKey(ctx.seed), 5)
    proj = tuple(jax.random.normal(ks[i], (T, k)) for i in range(3))
    nodes = []
    for j, w in enumerate((a.d_model, a.d_ff)):
        kk = jax.random.split(ks[3 + j], 5)
        nodes.append(tuple(jax.random.normal(kk[i], (w, k))
                           for i in range(3))
                     + (jax.random.normal(kk[3], (T, w), a.dtype),
                        jax.random.normal(kk[4], (k,))))
    secs = ctx.seconds_alone("bench_sketch_update",
                             jax.jit(bench_sketch_update),
                             (nodes, proj, jnp.asarray(k, jnp.int32)),
                             reps=20)
    if not secs:
        return None
    act = jnp.dtype(a.dtype).itemsize
    need = [flops.sketch_update(T, w, k, act_bytes=act)
            for w in (a.d_model, a.d_ff)]
    least = sum(peaks.least_seconds(n["flops"], n["bytes"],
                                    ctx.device_kind) for n in need)
    return 100.0 * least / secs
