"""adamw_roofline: `adamw_update` run alone on the cell's parameter
tree (parameters and moments donated, as in the step): the least time
for its operations and bytes (`flops.adamw`) over its device time."""
import jax

from bench import flops, peaks
from repro.models.transformer import init_params
from repro.optim.adamw import adamw_update, init_adamw


def read(ctx):
    cfg = ctx.run.optimizer

    def bench_adamw(params, grads, opt):
        p, o, _ = adamw_update(params, grads, opt, cfg, 1.0)
        return p, o

    key = jax.random.PRNGKey(ctx.seed)
    params = jax.jit(lambda k: init_params(k, ctx.arch))(key)
    grads = jax.jit(lambda p: jax.tree.map(
        lambda x: 1e-3 * jax.random.normal(key, x.shape, x.dtype), p))(
            params)
    opt = jax.jit(lambda p: init_adamw(p, cfg))(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    secs = ctx.seconds_alone(
        "bench_adamw", jax.jit(bench_adamw, donate_argnums=(0, 2)),
        (params, grads, opt), feed=lambda out, a: (out[0], a[1], out[1]))
    if not secs:
        return None
    need = flops.adamw(n)
    return 100.0 * peaks.least_seconds(need["flops"], need["bytes"],
                                       ctx.device_kind) / secs
