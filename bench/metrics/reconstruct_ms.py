"""reconstruct_ms: device milliseconds per training step of the
reconstructions the sketched backward makes: `reconstruct` run alone at
each sketched linear's node shape with the step's settings, one call
per sketched linear per layer."""
import jax
import jax.numpy as jnp

from repro.core.reconstruct import reconstruct


def read(ctx):
    t = ctx.cell.traffic
    if not t["sketch"]:
        return None

    def bench_reconstruct(x, y, z, omega, k_active):
        r = reconstruct(x, y, z, omega, k_active, mode=t["recon_mode"],
                        ridge=t["ridge"])
        return r.left, r.right

    a = ctx.arch
    T, k = t["batch"] * t["seq_len"], t["k_max"]
    # the linears each node feeds: MLP input -> gate and up (SwiGLU) or
    # up (GELU); MLP hidden -> down
    uses = {a.d_model: 2 if a.mlp_type == "swiglu" else 1, a.d_ff: 1}
    key = jax.random.PRNGKey(ctx.seed)
    omega = jax.random.normal(key, (T, k))
    k_active = jnp.asarray(k, jnp.int32)
    fn = jax.jit(bench_reconstruct)
    per_layer = 0.0
    for j, (w, n) in enumerate(uses.items()):
        x, y, z = (jax.random.normal(kk, (w, k)) for kk in
                   jax.random.split(jax.random.fold_in(key, j + 1), 3))
        secs = ctx.seconds_alone("bench_reconstruct", fn,
                                 (x, y, z, omega, k_active), reps=20)
        if not secs:
            return None
        per_layer += n * secs
    return 1e3 * a.num_layers * per_layer
