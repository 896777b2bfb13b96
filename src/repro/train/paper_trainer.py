"""Paper-faithful MLP trainer (§5.1 experimental variants).

Variants:
  standard          exact backprop (baseline)
  sketched_fixed    Algorithm 1 with fixed rank r
  sketched_adaptive + the adaptive rank controller (§4.3)
  monitor           exact backprop + monitoring-only sketches (PINN mode)
  corange           beyond-paper: sketched backprop with the Tropp
                    co-range triple (provable sqrt(6)-tail bound)

Sketching is per-NODE: each hidden activation node n (input to layer n+1)
owns an EMA triple; layer l >= 1 reconstructs its input from node l-1's
triple. This is the paper's per-layer (X^[l], Y^[l-1], Z^[l-1]) grouping
re-indexed by node (DESIGN.md §1).

Since the NodeTree unification (DESIGN.md §6) this module is a THIN
driver: every variant is just a NodeTree configuration —
  standard          no tree consulted
  monitor           paper-kind tree, updates only (exact backprop)
  sketched_*        paper-kind tree + sketched_matmul consumption
  corange           corange-kind tree + lowrank_grad_matmul
— and the update/refresh/monitoring machinery is the shared one in
repro.sketches / core.monitor.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.paper import ConvConfig, MLPConfig
from repro.core.adaptive import AdaptiveConfig, adaptive_step, \
    init_adaptive_state
from repro.core.corange import (
    corange_reconstruct, make_corange_projections, s_of,
)
from repro.core.monitor import (
    init_monitor_state, monitor_record, tree_metrics,
)
from repro.core.sketch import SketchConfig
from repro.models.mlp import _act, conv_im2col_sketched, im2col, \
    mlp_init
from repro.optim.adamw import AdamWConfig, adamw_update, init_adamw, \
    sgd_update
from repro.sketches import (
    NodeTree, SketchNode, corange_triple_update, init_node_tree,
    node_specs_for, pad_activation_rows, proj_num_tokens,
    proj_triple_update, refresh_tree, sketched_matmul,
)

Array = jax.Array


# -- low-rank grad matmul for the corange variant ---------------------------


@jax.custom_vjp
def lowrank_grad_matmul(x, w, left, right):
    """y = x @ w, but grad_w = right @ (left^T @ g) with A~ = left right^T
    (the reconstruction is computed before the call; residuals are the
    k-sized factors, never x)."""
    return x @ w


def _lr_fwd(x, w, left, right):
    return x @ w, (w, left, right)


def _lr_bwd(res, g):
    w, left, right = res
    grad_w = right @ (left.T @ g.astype(left.dtype))
    return g @ w.T, grad_w.astype(w.dtype), \
        jnp.zeros_like(left), jnp.zeros_like(right)


lowrank_grad_matmul.defvjp(_lr_fwd, _lr_bwd)


# -- sketch state ------------------------------------------------------------


def init_mlp_sketch(key, cfg: MLPConfig, scfg: SketchConfig,
                    variant: str) -> NodeTree:
    """NodeTree for the paper MLPs — one stacked "hidden" node.

    RNG protocol is frozen (fixed-seed baselines depend on it):
    split(key, 6); paper proj from ks[0..2], psi from ks[3]; corange
    projections all from ks[0]. ``scfg.proj_kind == "psparse"`` derives
    its hash coefficients from ks[4] (previously unused) so the
    gaussian/corange lineages — and their pinned baselines — are
    byte-identical across this PR (DESIGN.md §13).
    """
    from repro.sketches import (
        init_psparse_projections, make_psparse_corange_projections,
    )

    spec = node_specs_for(cfg)["hidden"]
    n_nodes, d = spec.layers, spec.width
    k_max = scfg.k_max
    psparse = scfg.proj_kind == "psparse"
    ks = jax.random.split(key, 6)
    if variant == "corange":
        if psparse:
            proj = make_psparse_corange_projections(
                ks[4], d, cfg.batch_size, k_max, scfg.proj_density)
        else:
            proj = make_corange_projections(ks[0], d, cfg.batch_size,
                                            k_max)
        node = SketchNode(
            x=jnp.zeros((n_nodes, k_max, cfg.batch_size)),
            y=jnp.zeros((n_nodes, d, k_max)),
            z=jnp.zeros((n_nodes, s_of(k_max), s_of(k_max))),
            psi=jnp.zeros((n_nodes, 0)),       # core weights live in proj
            kind="corange",
        )
    else:
        if psparse:
            proj = init_psparse_projections(
                ks[4], cfg.batch_size, k_max, scfg.proj_density)
        else:
            proj = {
                "upsilon": jax.random.normal(ks[0],
                                             (cfg.batch_size, k_max)),
                "omega": jax.random.normal(ks[1],
                                           (cfg.batch_size, k_max)),
                "phi": jax.random.normal(ks[2],
                                         (cfg.batch_size, k_max)),
            }
        # three distinct buffers (aliasing breaks donation — node.py)
        node = SketchNode(
            x=jnp.zeros((n_nodes, d, k_max)),
            y=jnp.zeros((n_nodes, d, k_max)),
            z=jnp.zeros((n_nodes, d, k_max)),
            psi=jax.random.normal(ks[3], (n_nodes, k_max)),
        )
    return NodeTree(
        nodes={"hidden": node},
        proj=proj,
        rank=jnp.asarray(scfg.rank, jnp.int32),
        key=key,
        epoch=jnp.asarray(0, jnp.int32),
        step=jnp.asarray(0, jnp.int32),
    )


# -- forward with sketched backward -----------------------------------------


def sketched_forward(params, x, sk: NodeTree, cfg: MLPConfig,
                     scfg: SketchConfig, variant: str, *,
                     dp_axis: str | None = None,
                     premerged: bool = False):
    """Returns (logits, new_sketch_state). The "hidden" node's triple for
    node l observes the activation feeding layer l+1; the canonical
    update in repro.sketches is the ONLY EMA math invoked here.

    The corange variant routes through the BATCHED reconstruction
    (`_corange_forward`): one vmapped `corange_reconstruct` over the
    stacked node instead of one solve per layer.

    DP layouts (DESIGN.md §4/§10): with ``dp_axis`` the per-token
    increments are psum-ed inside each `ema_triple_update` — the
    per-node reference. With ``premerged`` the incoming tree already
    holds THIS step's merged triples (folded in after the overlap
    schedule's early flat psum): consume them as-is, emit no updates —
    the returned state is the input tree unchanged."""
    if variant == "corange":
        if dp_axis is not None or premerged:
            raise ValueError(
                "the corange variant has no per-node DP reference path "
                "— its overlap coverage is the subsystem-level "
                "differential (tests/test_distributed.py)")
        return _corange_forward(params, x, sk, cfg, scfg, batched=True)
    act = _act(cfg.activation)
    k_active = sk.k_active
    hidden = sk.nodes["hidden"]
    n = len(params)
    h = x
    xs_new, ys_new, zs_new = [], [], []
    for i, p in enumerate(params):
        node = i - 1                       # node feeding layer i
        if 1 <= i and variant in ("sketched_fixed", "sketched_adaptive",
                                  "monitor"):
            if premerged:
                xc, yc, zc = (hidden.x[node], hidden.y[node],
                              hidden.z[node])
            else:
                xc, yc, zc = proj_triple_update(
                    hidden.x[node], hidden.y[node], hidden.z[node], h,
                    sk.proj, hidden.psi[node], scfg.beta,
                    k_active, axis_name=dp_axis)
            if variant == "monitor":
                z = h @ p["w"] + p["bias"]
            else:
                z = sketched_matmul(
                    h, p["w"], xc, yc, zc, sk.proj["omega"],
                    k_active, scfg.recon_mode, scfg.ridge, True
                ) + p["bias"]
            if not premerged:
                xs_new.append(xc), ys_new.append(yc), zs_new.append(zc)
        else:
            z = h @ p["w"] + p["bias"]
        h = act(z) if i < n - 1 else z
    if premerged:
        return h, sk
    if xs_new:
        hidden = dataclasses.replace(
            hidden, x=jnp.stack(xs_new), y=jnp.stack(ys_new),
            z=jnp.stack(zs_new))
    return h, dataclasses.replace(sk, nodes={"hidden": hidden},
                                  step=sk.step + 1)


def mlp_sketch_increments(params, x, sk: NodeTree, cfg: MLPConfig,
                          scfg: SketchConfig) -> NodeTree:
    """Phase 1 of the overlap schedule for the paper MLPs (DESIGN.md
    §10): the stop-gradient activation sweep (same observations the
    inline path sees — the primal never depends on any triple) followed
    by each node's LOCAL masked ``(1-beta)``-scaled increments, stacked
    into the "hidden" node's x/y/z slots with the step counter
    advanced. The per-layer loop mirrors `sketched_forward`'s update
    order exactly, so psum-merging these increments and folding them in
    (`ema_apply_increment`) is bitwise the per-node DP path."""
    from repro.sketches.update import (
        corange_triple_increment, proj_triple_increment,
    )

    act = _act(cfg.activation)
    hidden = sk.nodes["hidden"]
    k_active = sk.k_active
    n = len(params)
    h = x
    obs = []
    for i, p in enumerate(params):
        if i >= 1:
            obs.append(jax.lax.stop_gradient(h))
        if i == n - 1:
            break
        h = act(h @ p["w"] + p["bias"])
    if hidden.kind == "corange":
        incs = [
            corange_triple_increment(
                hidden.x[l], hidden.y[l], hidden.z[l], obs[l],
                sk.proj, scfg.beta, k_active)
            for l in range(len(obs))
        ]
    else:
        incs = [
            proj_triple_increment(
                hidden.x[l], hidden.y[l], hidden.z[l], obs[l],
                sk.proj, hidden.psi[l], scfg.beta, k_active)
            for l in range(len(obs))
        ]
    node = dataclasses.replace(
        hidden,
        x=jnp.stack([i[0] for i in incs]),
        y=jnp.stack([i[1] for i in incs]),
        z=jnp.stack([i[2] for i in incs]),
    )
    return dataclasses.replace(sk, nodes={"hidden": node},
                               step=sk.step + 1)


def _corange_forward(params, x, sk: NodeTree, cfg: MLPConfig,
                     scfg: SketchConfig, *, batched: bool):
    """Corange-variant forward.

    ``batched=True`` (production): the per-layer reconstruct loop is
    replaced by ONE vmapped reconstruction over the stacked SketchNode.
    The observed activations are the PRIMAL hidden states, which do not
    depend on any reconstruction (`lowrank_grad_matmul`'s primal is a
    plain matmul), so the chain splits into three phases with no cycle:

      1. stop-gradient activation sweep — collect every node's observed
         activation (bitwise the same values the differentiable chain
         recomputes in phase 3; XLA CSEs the duplicate matmuls);
      2. one batched `corange_triple_update` + ONE batched
         `corange_reconstruct` over the (L,)-stacked triple;
      3. the differentiable chain, consuming the precomputed per-layer
         (left, right) factors in `lowrank_grad_matmul`.

    ``batched=False`` keeps the PR 3 sequential update-reconstruct-
    consume loop as the parity reference (tests/test_reconstruct.py
    diffs the two at 1e-6 and asserts the jaxpr solve counts).
    """
    from repro.core.corange import corange_reconstruct_batched

    act = _act(cfg.activation)
    k_active = sk.k_active
    hidden = sk.nodes["hidden"]
    n = len(params)

    if not batched:                       # sequential reference
        h = x
        xs_new, ys_new, zs_new = [], [], []
        for i, p in enumerate(params):
            node = i - 1
            if i >= 1:
                xc, yc, zc = corange_triple_update(
                    hidden.x[node], hidden.y[node], hidden.z[node], h,
                    sk.proj, scfg.beta, k_active)
                rec = corange_reconstruct(xc, yc, zc, sk.proj, k_active)
                z = lowrank_grad_matmul(
                    h, p["w"], rec.left.astype(h.dtype),
                    rec.right.astype(h.dtype)) + p["bias"]
                xs_new.append(xc), ys_new.append(yc), zs_new.append(zc)
            else:
                z = h @ p["w"] + p["bias"]
            h = act(z) if i < n - 1 else z
        hidden = dataclasses.replace(
            hidden, x=jnp.stack(xs_new), y=jnp.stack(ys_new),
            z=jnp.stack(zs_new))
        return h, dataclasses.replace(sk, nodes={"hidden": hidden},
                                      step=sk.step + 1)

    # phase 1: observed activations (no AD path — updates stop-grad
    # their observation anyway)
    h = x
    obs = []
    for i, p in enumerate(params):
        if i >= 1:
            obs.append(h)
        if i == n - 1:
            break
        h = act(h @ p["w"] + p["bias"])
    obs = jax.lax.stop_gradient(jnp.stack(obs))        # (L, N_b, d)

    # phase 2: one batched update + ONE batched reconstruction
    xcs, ycs, zcs = jax.vmap(
        lambda xc, yc, zc, a: corange_triple_update(
            xc, yc, zc, a, sk.proj, scfg.beta, k_active)
    )(hidden.x, hidden.y, hidden.z, obs)
    rec = corange_reconstruct_batched(xcs, ycs, zcs, sk.proj, k_active)

    # phase 3: differentiable chain consuming the per-layer factors
    h = x
    for i, p in enumerate(params):
        if i >= 1:
            z = lowrank_grad_matmul(
                h, p["w"], rec.left[i - 1].astype(h.dtype),
                rec.right[i - 1].astype(h.dtype)) + p["bias"]
        else:
            z = h @ p["w"] + p["bias"]
        h = act(z) if i < n - 1 else z
    hidden = dataclasses.replace(hidden, x=xcs, y=ycs, z=zcs)
    return h, dataclasses.replace(sk, nodes={"hidden": hidden},
                                  step=sk.step + 1)


def plain_forward(params, x, cfg: MLPConfig):
    act = _act(cfg.activation)
    h = x
    n = len(params)
    for i, p in enumerate(params):
        z = h @ p["w"] + p["bias"]
        h = act(z) if i < n - 1 else z
    return h


# -- training step -----------------------------------------------------------


def ce_loss(logits, y):
    ls = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(ls, y[:, None], 1).mean()


def make_step(cfg: MLPConfig, scfg: SketchConfig, variant: str,
              opt_cfg: AdamWConfig):
    def step(params, opt, sk, x, y):
        def loss_fn(p):
            if variant == "standard":
                return ce_loss(plain_forward(p, x, cfg), y), sk
            logits, new_sk = sketched_forward(p, x, sk, cfg, scfg, variant)
            return ce_loss(logits, y), new_sk

        (loss, new_sk), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        if cfg.optimizer == "adam":
            params, opt, _ = adamw_update(params, grads, opt, opt_cfg)
        else:
            params = sgd_update(params, grads, opt_cfg.lr)
        return params, opt, new_sk, loss

    return jax.jit(step)


def make_dp_step(cfg: MLPConfig, scfg: SketchConfig, variant: str,
                 opt_cfg: AdamWConfig, mesh, *, axis: str = "data",
                 collective: str = "overlap"):
    """W-way data-parallel MLP train step — the differential tier's MLP
    half (DESIGN.md §10). The train state is replicated; the batch is
    split on its leading axis.

      * ``collective="per_node"``: the DP-exact reference — one psum
        per node inside `sketched_forward` (`ema_triple_update` with
        ``axis_name``), then a dense gradient/loss pmean.
      * ``collective="overlap"``: phase 1 sweeps the activations and
        issues the sketch-increment flat psum immediately
        (barrier-pinned, hideable behind the backward); the merged
        triples are folded in and phase 2's backward consumes THEM
        through `sketched_matmul` — current-step DP-exact consumption,
        bitwise equal to per_node — before the gradient wire + loss
        ride the second, post-backward psum.

    Differential contract (tests/test_distributed.py): the SKETCH TREES
    and the loss are bitwise identical between the two layouts at any
    worker count; the gradient-derived leaves (params, Adam moments)
    agree to last-ulp compiler noise only — the freely-inlined MLP
    backward is re-fused by XLA per program, unlike the LM's
    scan/remat-bounded backward, which IS bitwise end to end."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.parallel.collectives import psum_flat_segments
    from repro.sketches.update import ema_apply_increment
    from repro.sketches.wire import tree_increment_leaves

    if variant not in ("sketched_fixed", "sketched_adaptive", "monitor"):
        raise ValueError(
            f"make_dp_step supports the paper-kind variants; got "
            f"{variant!r} (corange's overlap coverage is the "
            f"subsystem-level differential)")
    if collective not in ("per_node", "overlap"):
        raise ValueError(
            f"collective must be 'per_node' or 'overlap', got "
            f"{collective!r}")

    def step(params, opt, sk, x, y):
        if collective == "per_node":
            def loss_fn(p):
                logits, new_sk = sketched_forward(
                    p, x, sk, cfg, scfg, variant, dp_axis=axis)
                return ce_loss(logits, y), new_sk

            (loss, new_sk), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            loss = jax.lax.pmean(loss, axis)
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis),
                                 grads)
        else:
            inc_tree = mlp_sketch_increments(params, x, sk, cfg, scfg)
            merged = psum_flat_segments(
                tree_increment_leaves(inc_tree), axis,
                name="overlap_sketch", barrier=True)
            m = merged["hidden"]
            old = sk.nodes["hidden"]
            ka = sk.k_active
            new_sk = dataclasses.replace(
                inc_tree,
                nodes={"hidden": dataclasses.replace(
                    inc_tree.nodes["hidden"],
                    x=ema_apply_increment(old.x, m["x"], scfg.beta, ka),
                    y=ema_apply_increment(old.y, m["y"], scfg.beta, ka),
                    z=ema_apply_increment(old.z, m["z"], scfg.beta, ka),
                )})

            def loss_fn(p):
                logits, _ = sketched_forward(
                    p, x, new_sk, cfg, scfg, variant, premerged=True)
                return ce_loss(logits, y)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            mg = psum_flat_segments(
                {"n": jnp.ones((), jnp.float32), "scalars": loss[None],
                 "grads": grads},
                axis, name="overlap_grad")
            loss = mg["scalars"][0] / mg["n"]
            grads = jax.tree.map(lambda g: g / mg["n"], mg["grads"])
        if cfg.optimizer == "adam":
            params, opt, _ = adamw_update(params, grads, opt, opt_cfg)
        else:
            params = sgd_update(params, grads, opt_cfg.lr)
        return params, opt, new_sk, loss

    return jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P(), P(axis), P(axis)),
        out_specs=(P(), P(), P(), P()),
        check_vma=False))


@dataclasses.dataclass
class PaperTrainResult:
    params: Any
    history: list
    sketch: Any
    monitor: Any


def train(cfg: MLPConfig, scfg: SketchConfig, variant: str, *,
          steps: int, batch_fn, eval_fn=None, seed: int = 0,
          steps_per_epoch: int = 50,
          adaptive: AdaptiveConfig | None = None,
          monitor_window: int = 64) -> PaperTrainResult:
    """Generic driver: batch_fn(key) -> (x, y); eval_fn(params) -> dict."""
    key = jax.random.PRNGKey(seed)
    kp, ks = jax.random.split(key)
    params = mlp_init(kp, cfg)
    opt_cfg = AdamWConfig(lr=cfg.learning_rate, b2=0.999)
    opt = init_adamw(params, opt_cfg)
    sk = init_mlp_sketch(ks, cfg, scfg, variant)
    astate = init_adaptive_state()
    monitor = init_monitor_state(monitor_window, cfg.num_hidden_layers)
    step = make_step(cfg, scfg, variant, opt_cfg)
    history = []
    for s in range(steps):
        x, y = batch_fn(jax.random.fold_in(key, s))
        params, opt, sk, loss = step(params, opt, sk, x, y)
        rec = {"step": s, "loss": float(loss),
               "rank": int(sk.rank)}
        if variant != "standard":
            monitor = monitor_record(monitor, tree_metrics(sk))
        if eval_fn is not None and (s + 1) % steps_per_epoch == 0:
            rec.update(eval_fn(params))
            if adaptive is not None and variant == "sketched_adaptive":
                astate, new_rank, changed = adaptive_step(
                    astate, sk.rank,
                    jnp.asarray(rec["loss"], jnp.float32), adaptive)
                sk = dataclasses.replace(sk, rank=new_rank)
                if bool(changed):
                    # paper Alg. 1 "reinitialize matrices": zero the
                    # sketches AND re-derive projections via fold_in —
                    # static shapes, so nothing recompiles
                    sk = refresh_tree(sk)
        history.append(rec)
    return PaperTrainResult(params=params, history=history, sketch=sk,
                            monitor=monitor)


def accuracy(params, cfg: MLPConfig, x, y) -> float:
    logits = plain_forward(params, x, cfg)
    return float((jnp.argmax(logits, -1) == y).mean())


# -- sketched conv trainer (DESIGN.md §15: XConv im2col factoring) ----------


def conv_init(key, cfg: ConvConfig):
    """Two SAME stride-1 conv stages (3x3, C->8->16) with 2x2 max-pool
    after each, plus one exact linear head. Only the conv stages are
    sketched (one node per stage, im2col patch width)."""
    ks = jax.random.split(key, 3)
    feat = (cfg.hw // 4) ** 2 * 16
    return {
        "c1": (jax.random.normal(ks[0], (3, 3, cfg.channels, 8))
               * (2.0 / (9 * cfg.channels)) ** 0.5).astype(cfg.dtype),
        "c2": (jax.random.normal(ks[1], (3, 3, 8, 16))
               * (2.0 / 72) ** 0.5).astype(cfg.dtype),
        "head": {
            # zero head: max-pooled ReLU features come in hot (pooling
            # keeps the largest of 4 positive values), so a fan-in init
            # starts at 2x the ln(d_out) plateau and the first steps
            # thrash; logits grow from 0 instead
            "w": jnp.zeros((feat, cfg.d_out), cfg.dtype),
            "bias": jnp.zeros((cfg.d_out,), cfg.dtype),
        },
    }


def init_conv_sketch(key, cfg: ConvConfig, scfg: SketchConfig) -> NodeTree:
    """NodeTree for the sketched conv stem — standard paper-kind tree
    via `init_node_tree` (so the frozen split(4+N) RNG protocol, refresh
    lineage, and checkpoint layout all apply unchanged). The row binding
    is ``cfg.num_tokens`` = B*hw^2, stage 1's im2col row count; stage 2
    zero-pads its B*(hw/2)^2 rows up to it."""
    tree = init_node_tree(
        key, node_specs_for(cfg), cfg.num_tokens, scfg.k_max,
        proj_kind=scfg.proj_kind, proj_density=scfg.proj_density)
    return dataclasses.replace(
        tree, rank=jnp.asarray(scfg.rank, jnp.int32))


def _pool2(h):
    return jax.lax.reduce_window(
        h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def conv_plain_forward(params, img, cfg: ConvConfig):
    h = img
    for wkey in ("c1", "c2"):
        h = jax.lax.conv_general_dilated(
            h, params[wkey], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = _pool2(jax.nn.relu(h))
    h = h.reshape(h.shape[0], -1)
    return h @ params["head"]["w"] + params["head"]["bias"]


def conv_sketched_forward(params, img, sk: NodeTree, cfg: ConvConfig,
                          scfg: SketchConfig):
    """Returns (logits, new_sketch_state). Each stage updates its node's
    triple on the zero-padded im2col patch matrix, then consumes the
    fresh triple through `conv_im2col_sketched` — the conv analogue of
    `sketched_forward`'s update-then-consume per-node loop."""
    k_active = sk.k_active
    num_tokens = proj_num_tokens(sk.proj)
    new_nodes = dict(sk.nodes)
    h = img
    for name, wkey in (("conv1", "c1"), ("conv2", "c2")):
        node = sk.nodes[name]
        patches = pad_activation_rows(
            im2col(h, 3, 3).astype(jnp.float32), num_tokens)
        xc, yc, zc = proj_triple_update(
            node.x, node.y, node.z, patches, sk.proj, node.psi,
            scfg.beta, k_active)
        node = dataclasses.replace(node, x=xc, y=yc, z=zc)
        new_nodes[name] = node
        h = conv_im2col_sketched(
            h, params[wkey], node, sk.proj, k_active,
            recon_mode=scfg.recon_mode, ridge=scfg.ridge, factored=True)
        h = _pool2(jax.nn.relu(h))
    h = h.reshape(h.shape[0], -1)
    logits = h @ params["head"]["w"] + params["head"]["bias"]
    return logits, dataclasses.replace(sk, nodes=new_nodes,
                                       step=sk.step + 1)


def make_conv_step(cfg: ConvConfig, scfg: SketchConfig, variant: str,
                   opt_cfg: AdamWConfig):
    def step(params, opt, sk, x, y):
        def loss_fn(p):
            if variant == "standard":
                return ce_loss(conv_plain_forward(p, x, cfg), y), sk
            logits, new_sk = conv_sketched_forward(p, x, sk, cfg, scfg)
            return ce_loss(logits, y), new_sk

        (loss, new_sk), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        params, opt, _ = adamw_update(params, grads, opt, opt_cfg)
        return params, opt, new_sk, loss

    return jax.jit(step)


def train_conv(cfg: ConvConfig, scfg: SketchConfig, variant: str, *,
               steps: int, batch_fn, seed: int = 0,
               monitor_window: int = 64) -> PaperTrainResult:
    """Conv-family driver, same contract as `train`:
    batch_fn(key) -> (img (B,hw,hw,C), labels (B,))."""
    key = jax.random.PRNGKey(seed)
    kp, ks = jax.random.split(key)
    params = conv_init(kp, cfg)
    opt_cfg = AdamWConfig(lr=cfg.learning_rate, b2=0.999)
    opt = init_adamw(params, opt_cfg)
    sk = init_conv_sketch(ks, cfg, scfg)
    monitor = init_monitor_state(monitor_window, len(sk.nodes))
    step = make_conv_step(cfg, scfg, variant, opt_cfg)
    history = []
    for s in range(steps):
        x, y = batch_fn(jax.random.fold_in(key, s))
        params, opt, sk, loss = step(params, opt, sk, x, y)
        history.append({"step": s, "loss": float(loss),
                        "rank": int(sk.rank)})
        if variant != "standard":
            monitor = monitor_record(monitor, tree_metrics(sk))
    return PaperTrainResult(params=params, history=history, sketch=sk,
                            monitor=monitor)
