"""Plain float32 reference of the cells' training step.

It follows the published layer equations of the repo's dense block
(RMSNorm, RoPE, grouped-query causal attention, SwiGLU or tanh-GELU MLP,
separate output head, cross-entropy with a z-loss), the paper's sketched
backprop for the MLP linears (EMA sketches, two-stage least-squares
reconstruction, gradient from the reconstructed activation) and AdamW
with global-norm clipping and a warmup-cosine schedule. It imports
nothing of the program: weights, projections and batches are drawn anew
from the seed with the same draws of `jax.random` that the program's
initialisers and data feed make, so the two see the same numbers.

Matmuls run at "highest" precision. `precision="fp8"` is the control:
every matmul operand rounded to float8 with a per-tensor scale (e4m3
forward, e5m2 for cotangents), the step below the bfloat16 that the
configuration states.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.compare import sample_index

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Spec:
    """Everything one reference step needs, read from the cell's files."""

    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    layers: int
    mlp: str                 # swiglu | gelu
    norm_eps: float
    rope_theta: float
    seq: int
    batch: int
    sketch: bool
    k: int
    beta: float
    ridge: float
    lr: float
    b1: float
    b2: float
    adam_eps: float
    weight_decay: float
    grad_clip: float
    warmup_steps: int
    total_steps: int
    z_weight: float

    @property
    def tokens(self) -> int:
        return self.batch * self.seq


# --------------------------------------------------------------------------
# precision
# --------------------------------------------------------------------------


def _q8(x, dtype):
    """Round to float8 with a per-tensor scale (amax to the format's max)."""
    big = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / big, 1.0)
    return (x / s).astype(dtype).astype(F32) * s


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum8(eq, a, b):
    return jnp.einsum(eq, _q8(a, jnp.float8_e4m3fn),
                      _q8(b, jnp.float8_e4m3fn), precision="highest")


def _einsum8_fwd(eq, a, b):
    qa, qb = _q8(a, jnp.float8_e4m3fn), _q8(b, jnp.float8_e4m3fn)
    return jnp.einsum(eq, qa, qb, precision="highest"), (qa, qb)


def _einsum8_bwd(eq, res, g):
    _, vjp = jax.vjp(
        lambda a, b: jnp.einsum(eq, a, b, precision="highest"), *res)
    return vjp(_q8(g, jnp.float8_e5m2))


_einsum8.defvjp(_einsum8_fwd, _einsum8_bwd)


def _einsum(eq, a, b, precision):
    """Two-operand einsum at the reference's precision."""
    if precision == "fp8":
        return _einsum8(eq, a, b)
    return jnp.einsum(eq, a, b, precision="highest")


def _dot(a, b, precision):
    return _einsum("ij,jk->ik", a, b, precision)


# --------------------------------------------------------------------------
# weights, projections and batches from the seed
# --------------------------------------------------------------------------


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape) * (1.0 / fan_in) ** 0.5


def param_recipes(spec: Spec, seed: int) -> dict:
    """{leaf name: (key, shape, fan_in)} for every random weight, in the
    key lineage of the program's initialiser: split(seed key) -> params,
    sketch; params -> embedding, blocks, tail; block l from
    fold_in(blocks, l), split 4 -> attention, MLP."""
    kp, _ = jax.random.split(jax.random.PRNGKey(seed))
    k_emb, k_blk, _ = jax.random.split(kp, 3)
    e1, e2 = jax.random.split(k_emb)
    d, V = spec.d, spec.vocab
    out = {"embed/embedding": (e1, (V, d), d),
           "embed/head": (e2, (V, d), d)}
    H, KV, hd, F = spec.heads, spec.kv_heads, spec.head_dim, spec.d_ff
    for l in range(spec.layers):
        ks = jax.random.split(jax.random.fold_in(k_blk, l), 4)
        ka = jax.random.split(ks[0], 4)
        km = jax.random.split(ks[1], 3)
        p = f"layer{l}/"
        out[p + "attn/wq"] = (ka[0], (d, H, hd), d)
        out[p + "attn/wk"] = (ka[1], (d, KV, hd), d)
        out[p + "attn/wv"] = (ka[2], (d, KV, hd), d)
        out[p + "attn/wo"] = (ka[3], (H, hd, d), H * hd)
        if spec.mlp == "swiglu":
            out[p + "mlp/w_gate"] = (km[0], (d, F), d)
            out[p + "mlp/w_up"] = (km[1], (d, F), d)
            out[p + "mlp/w_down"] = (km[2], (F, d), F)
        else:
            out[p + "mlp/w_up"] = (km[0], (d, F), d)
            out[p + "mlp/w_down"] = (km[1], (F, d), F)
    return out


def norm_names(spec: Spec) -> list[str]:
    names = ["final_norm/scale"]
    for l in range(spec.layers):
        names += [f"layer{l}/norm1/scale", f"layer{l}/norm2/scale"]
    return names


def init_leaf(spec: Spec, seed: int, name: str):
    """One initial weight, made alone (the change after the steps is
    measured against it leaf by leaf, so no second copy is held)."""
    if name.endswith("norm1/scale") or name.endswith("norm2/scale") \
            or name == "final_norm/scale":
        return jnp.ones((spec.d,), F32)
    key, shape, fan_in = param_recipes(spec, seed)[name]
    return _normal(key, shape, fan_in)


def init_params(spec: Spec, seed: int) -> dict:
    p = {n: _normal(k, s, f) for n, (k, s, f)
         in param_recipes(spec, seed).items()}
    for n in norm_names(spec):
        p[n] = jnp.ones((spec.d,), F32)
    return p


def init_sketch(spec: Spec, seed: int) -> dict | None:
    """Zero EMA triples and Gaussian projections: split(sketch key, 6) ->
    upsilon, omega, phi, (unused), psi of the MLP input node, psi of the
    MLP hidden node."""
    if not spec.sketch:
        return None
    _, ks = jax.random.split(jax.random.PRNGKey(seed))
    kk = jax.random.split(ks, 6)
    T, k, L = spec.tokens, spec.k, spec.layers
    out = {"upsilon": jax.random.normal(kk[0], (T, k)),
           "omega": jax.random.normal(kk[1], (T, k)),
           "phi": jax.random.normal(kk[2], (T, k))}
    for node, i, w in (("in", 4, spec.d), ("h", 5, spec.d_ff)):
        out[f"psi_{node}"] = jax.random.normal(kk[i], (L, k))
        for t in "xyz":
            out[f"{t}_{node}"] = jnp.zeros((L, w, k), F32)
    return out


def batch(spec: Spec, seed: int, step: int, alter_token: bool = False):
    """The synthetic LM batch of one step: uniform tokens with an
    8-token motif spliced into every third position."""
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), step), 0)
    k1, k2 = jax.random.split(key)
    B, S, V = spec.batch, spec.seq, spec.vocab
    base = jax.random.randint(k1, (B, S + 1), 0, V)
    motif = jax.random.randint(k2, (B, 8), 0, V)
    pattern = jnp.tile(motif, (1, (S + 8) // 8))[:, : S + 1]
    seq = jnp.where((jnp.arange(S + 1) % 3 == 0)[None, :], pattern, base)
    seq = seq.astype(jnp.int32)
    if alter_token:
        seq = seq.at[:, S // 2].set((seq[:, S // 2] + 1) % V)
    return seq[:, :-1], seq[:, 1:]


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x (B, S, H, D): rotate the two halves of each head."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _proj(x, w, precision):
    """(B, S, d) @ (d, ...) as one 2-D matmul."""
    B, S, d = x.shape
    out = _dot(x.reshape(B * S, d), w.reshape(d, -1), precision)
    return out.reshape((B, S) + w.shape[1:])


def _attention(q, k, v, precision, block=512):
    """Causal softmax attention; q (B,S,H,D), k/v (B,S,KV,D). Query rows
    run in blocks, each recomputed in the backward, so long sequences
    fit."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    block = min(block, S)
    qs = (q * D ** -0.5).reshape(B, S // block, block, KV, G, D)
    kk = k.transpose(0, 2, 1, 3)                     # (B, KV, S, D)
    vv = v.transpose(0, 2, 1, 3)

    @jax.checkpoint
    def one(i, qb):                                  # qb (B, blk, KV, G, D)
        s = _einsum("bqhgd,bhkd->bhgqk", qb, kk, precision)
        rows = i * block + jnp.arange(block)
        mask = rows[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = _einsum("bhgqk,bhkd->bhgqd", p, vv, precision)
        return o.transpose(0, 3, 1, 2, 4)            # (B, blk, KV, G, D)

    out = jax.lax.map(lambda a: one(*a),
                      (jnp.arange(S // block), qs.swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(B, S, H, D)


def reconstruct(x_s, y_s, z_s, omega, ridge):
    """The node's (T, d) activation from its EMA triple: two-stage
    least squares over QR bases, with the pseudo-inverse of Y taken by
    ridge-regularised normal equations (ridge relative to tr(YᵀY)/k)."""
    hi = partial(jnp.matmul, precision="highest")
    q_y = jnp.linalg.qr(y_s)[0]
    c_inter = hi(q_y.T, z_s)
    p_x = jnp.linalg.qr(x_s.T)[0]
    c = hi(p_x.T, c_inter.T)
    q_x = jnp.linalg.qr(x_s)[0]
    g = hi(y_s.T, y_s)
    k = g.shape[0]
    lam = ridge * (jnp.trace(g) / k + 1e-30)
    ypq = jnp.linalg.solve(g + lam * jnp.eye(k, dtype=g.dtype),
                           hi(y_s.T, q_y))
    return hi(hi(omega, hi(ypq, c)), q_x.T)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _sketched(a, w, x_s, y_s, z_s, omega, ridge, precision):
    return _dot(a, w, precision)


def _sketched_fwd(a, w, x_s, y_s, z_s, omega, ridge, precision):
    return _dot(a, w, precision), (w, x_s, y_s, z_s, omega)


def _sketched_bwd(ridge, precision, res, g):
    w, x_s, y_s, z_s, omega = res
    a_rec = reconstruct(x_s, y_s, z_s, omega, ridge)
    zero = jnp.zeros_like
    return (_dot(g, w.T, precision), _dot(a_rec.T, g, precision),
            zero(x_s), zero(y_s), zero(z_s), zero(omega))


_sketched.defvjp(_sketched_fwd, _sketched_bwd)


def _ema(sk, node, l, a, beta):
    """EMA update of layer l's triple of `node` from activation a (T, w).
    Returns the new (x, y, z)."""
    a = jax.lax.stop_gradient(a)
    hi = partial(jnp.matmul, precision="highest")
    x = beta * sk[f"x_{node}"][l] + (1 - beta) * hi(a.T, sk["upsilon"])
    y = beta * sk[f"y_{node}"][l] + (1 - beta) * hi(a.T, sk["omega"])
    z = beta * sk[f"z_{node}"][l] + (1 - beta) * (
        hi(a.T, sk["phi"]) * sk[f"psi_{node}"][l][None, :])
    return x, y, z


def _mlp(spec, p, pre, h2, sk, l, precision):
    """Returns (y, new triples of layer l or {})."""
    B, S, d = h2.shape
    a = h2.reshape(B * S, d)
    act = ((lambda g, u: jax.nn.silu(g) * u) if spec.mlp == "swiglu"
           else (lambda g, u: jax.nn.gelu(u, approximate=True)))
    if sk is None:
        up = _dot(a, p[pre + "w_up"], precision)
        gate = _dot(a, p[pre + "w_gate"], precision) \
            if spec.mlp == "swiglu" else None
        h = act(gate, up)
        return _dot(h, p[pre + "w_down"], precision).reshape(B, S, d), {}
    omega = sk["omega"]
    t_in = _ema(sk, "in", l, a, spec.beta)
    lin = lambda x, w, t: _sketched(x, w, *t, omega, spec.ridge, precision)
    up = lin(a, p[pre + "w_up"], t_in)
    gate = lin(a, p[pre + "w_gate"], t_in) if spec.mlp == "swiglu" \
        else None
    h = act(gate, up)
    t_h = _ema(sk, "h", l, h, spec.beta)
    y = lin(h, p[pre + "w_down"], t_h)
    return y.reshape(B, S, d), {"in": t_in, "h": t_h}


def loss_fn(params, sk, tokens, labels, spec: Spec, precision="f32",
            keep=None):
    """Mean next-token cross-entropy + z-loss, and the updated sketch.
    `keep` (S,) bool restricts the mean to some positions (a fault)."""
    p = params
    B, S = tokens.shape
    x = p["embed/embedding"][tokens] * spec.d ** 0.5
    new = {}
    for l in range(spec.layers):
        pre = f"layer{l}/"

        def attn_block(x, p=p, pre=pre):
            h = _rms(x, p[pre + "norm1/scale"], spec.norm_eps)
            q = _rope(_proj(h, p[pre + "attn/wq"], precision),
                      spec.rope_theta)
            k = _rope(_proj(h, p[pre + "attn/wk"], precision),
                      spec.rope_theta)
            v = _proj(h, p[pre + "attn/wv"], precision)
            o = _attention(q, k, v, precision)
            H, hd = spec.heads, spec.head_dim
            wo = p[pre + "attn/wo"].reshape(H * hd, spec.d)
            return x + _dot(o.reshape(B * S, H * hd), wo,
                            precision).reshape(B, S, spec.d)

        x = jax.checkpoint(attn_block)(x)
        h2 = _rms(x, p[pre + "norm2/scale"], spec.norm_eps)
        y, t = _mlp(spec, p, pre + "mlp/", h2, sk, l, precision)
        new[l] = t
        x = x + y
    x = _rms(x, p["final_norm/scale"], spec.norm_eps)
    logits = _dot(x.reshape(B * S, spec.d), p["embed/head"].T, precision)
    lse = jax.nn.logsumexp(logits, -1)
    true = jnp.take_along_axis(logits, labels.reshape(-1, 1), -1)[:, 0]
    w = jnp.ones((B, S), F32) if keep is None else \
        jnp.broadcast_to(keep.astype(F32)[None, :], (B, S))
    w = w.reshape(-1) / jnp.sum(w)
    ce = jnp.sum((lse - true) * w)
    loss = ce + spec.z_weight * jnp.sum(lse * lse * w)
    new_sk = None
    if sk is not None:
        new_sk = dict(sk)
        for node in ("in", "h"):
            for i, t in enumerate("xyz"):
                new_sk[f"{t}_{node}"] = jnp.stack(
                    [new[l][node][i] for l in range(spec.layers)])
    return loss, new_sk


def lr_scale(spec: Spec, step: int) -> float:
    """Linear warmup, then cosine down to a tenth."""
    if step < spec.warmup_steps:
        return step / max(spec.warmup_steps, 1)
    t = (step - spec.warmup_steps) / max(
        spec.total_steps - spec.warmup_steps, 1)
    t = min(max(t, 0.0), 1.0)
    return 0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * t))


def adamw(params, grads, m, v, count, spec: Spec, scale: float):
    """AdamW after clipping the gradient's global norm. Returns
    (params, m, v, norm before clipping)."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    if spec.grad_clip > 0:
        c = jnp.minimum(1.0, spec.grad_clip / jnp.maximum(gn, 1e-12))
        grads = {n: g * c for n, g in grads.items()}
    b1c = 1.0 - spec.b1 ** count
    b2c = 1.0 - spec.b2 ** count
    lr = spec.lr * scale
    new_p, new_m, new_v = {}, {}, {}
    for n, g in grads.items():
        mm = spec.b1 * m[n] + (1 - spec.b1) * g
        vv = spec.b2 * v[n] + (1 - spec.b2) * g * g
        upd = (mm / b1c) / (jnp.sqrt(vv / b2c) + spec.adam_eps)
        new_p[n] = params[n] - lr * (upd + spec.weight_decay * params[n])
        new_m[n], new_v[n] = mm, vv
    return new_p, new_m, new_v, gn


def make_step(spec: Spec, precision="f32", half_batch=False,
              donate=True):
    """One jitted training step: (params, m, v, sketch, tokens, labels,
    count, lr scale) -> (params, m, v, sketch, loss, grad norm). The
    parameters and moments are donated unless `donate` is False."""
    keep = None
    if half_batch:
        keep = jnp.arange(spec.seq) < spec.seq // 2

    def step(params, m, v, sk, tokens, labels, count, scale):
        (loss, new_sk), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, sk, tokens, labels, spec,
                                   precision, keep)
        params, m, v, gn = adamw(params, grads, m, v, count, spec, scale)
        return params, m, v, new_sk, loss, gn

    return jax.jit(step, donate_argnums=(0, 1, 2) if donate else ())


@jax.jit
def _leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(x))) for n, x in tree.items()}


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a - b)))


def run(spec: Spec, seed: int, steps: int = 3, precision="f32",
        fault: str | None = None) -> dict:
    """The reference's readings over the first `steps` steps: loss and
    gradient norm (before clipping) per step, the first gradient's norm
    per leaf as AdamW gets it (after clipping; read from the first
    moment after one step) and its elements at `compare.sample_index`,
    each leaf's change after `steps` steps, and the batches it trained
    on.

    `fault` plants one of the faults the comparison must catch:
    "frozen" (every step returns the state it was given), "half_batch"
    (half the positions of every row left out of the mean),
    "altered_token" (one token of every batch altered where the batch
    is made)."""
    params = init_params(spec, seed)
    m = {n: jnp.zeros_like(x) for n, x in params.items()}
    v = {n: jnp.zeros_like(x) for n, x in params.items()}
    sk = init_sketch(spec, seed)
    frozen = fault == "frozen"
    step_fn = make_step(spec, precision, half_batch=(fault == "half_batch"),
                        donate=not frozen)
    losses, gnorms, first, batches = [], [], None, []
    for s in range(steps):
        tokens, labels = batch(spec, seed, s,
                               alter_token=(fault == "altered_token"))
        batches.append((np.asarray(tokens), np.asarray(labels)))
        out = step_fn(params, m, v, sk, tokens, labels,
                      jnp.float32(s + 1), jnp.float32(lr_scale(spec, s)))
        if not frozen:
            params, m, v, sk = out[:4]
        losses.append(float(out[4]))
        gnorms.append(float(out[5]))
        del out
        if s == 0:
            first = {n: float(x) / (1 - spec.b1)
                     for n, x in _leaf_norms(m).items()}
            first_sample = {
                n: np.asarray(x.reshape(-1)[sample_index(x.size)],
                              np.float64) / (1 - spec.b1)
                for n, x in m.items()}
    del m, v
    change = {n: float(_diff_norm(params[n], init_leaf(spec, seed, n)))
              for n in list(params)}
    return {"loss": losses, "grad_norm": gnorms, "first_grad": first,
            "first_sample": first_sample, "change": change,
            "batches": batches}
