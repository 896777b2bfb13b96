"""Logical-axis sharding rules (Megatron-TP + sequence-parallel + ZeRO-3).

Model code never names mesh axes directly: it calls ``constrain(x, axes)``
with LOGICAL axis names; the active ``ShardingRules`` (installed via the
``use_rules`` context or passed explicitly) maps them to mesh axes. With no
rules installed, ``constrain`` is the identity, so the same model code runs
un-sharded on one CPU device for smoke tests.

Logical activation axes
    batch     -> ("pod","data")  [dp]
    seq_sp    -> "model"         sequence-parallel residual stream
    embed_act -> None            activation feature dim
    heads_act -> "model"         attention heads in flight
    vocab_act -> "model"         logits vocab dim
    expert_act-> "model"         dispatched expert dim (EP)
    kvseq     -> "model"         sequence-sharded KV cache (kv_heads < TP)
    none      -> None

Parameter leaves are sharded by name via ``spec_for_param`` (ZeRO-3: the
``embed``/input feature dim of every weight is sharded over dp in addition
to the tensor-parallel dim; XLA SPMD then materializes per-group
all-gathers inside the scan body so only one group's weights are live).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    mesh: Mesh
    dp_axes: tuple[str, ...]          # ("data",) or ("pod", "data")
    tp_axis: str = "model"
    zero3: bool = True                # shard params over dp too (FSDP)
    sequence_parallel: bool = True    # residual stream seq-sharded over TP
    # "megatron": TP weights + SP residual (activation gathers at block
    #             boundaries) — the baseline.
    # "fsdp":     weights fully sharded over dp x tp and gathered per
    #             layer; activations stay token-sharded end-to-end (the
    #             §Perf beyond-paper strategy: gathering 100s-MB weights
    #             beats gathering 10s-GB full-sequence activations).
    strategy: str = "megatron"

    @property
    def dp(self):
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]

    @property
    def dp_size(self) -> int:
        n = 1
        for a in self.dp_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis]

    def act_axis(self, name: str):
        """Map a logical ACTIVATION axis name to a mesh axis (or None)."""
        table = {
            "batch": self.dp,
            "seq_sp": self.tp_axis if self.sequence_parallel else None,
            # seq dim of attention/FFN intermediates: gathered under
            # megatron (feature dims carry TP), sharded under fsdp
            "seq_attn": None,
            # flattened token dim (B*S): dp under megatron (seq gathered),
            # dp x tp under fsdp
            "tokens": self.dp,
            "heads_act": self.tp_axis,
            "vocab_act": self.tp_axis,
            "expert_act": self.tp_axis,
            "mlp_act": self.tp_axis,
            # recurrent-block feature dims keep TP under BOTH strategies
            "rnn_feat": self.tp_axis,
            "kvseq": self.tp_axis,
            "embed_act": None,
            "none": None,
        }
        if self.strategy == "fsdp":
            # activations stay token-sharded; no feature-dim TP in flight
            table.update(
                heads_act=None, vocab_act=None, mlp_act=None,
                seq_attn=self.tp_axis,
                tokens=tuple(self.dp_axes) + (self.tp_axis,),
            )
        return table[name]

    def logits_axes(self) -> tuple[str, str, str]:
        """Sharding of (B, S, V) logits: vocab-TP under megatron (seq was
        gathered for the unembed matmul), seq-sharded under fsdp (full
        vocab locally — CE softmax needs no collective)."""
        if self.strategy == "fsdp":
            return ("batch", "seq_sp", "none")
        return ("batch", "none", "vocab_act")

    def spec(self, *axes: str) -> P:
        return P(*(self.act_axis(a) for a in axes))


_tls = threading.local()


def current_rules() -> ShardingRules | None:
    return getattr(_tls, "rules", None)


@contextlib.contextmanager
def use_rules(rules: ShardingRules | None):
    prev = current_rules()
    _tls.rules = rules
    try:
        yield rules
    finally:
        _tls.rules = prev


def constrain(x, *axes: str):
    """with_sharding_constraint by logical axis names; identity w/o rules.

    Axis count must match x.ndim. Dims whose size does not divide the mesh
    axis are silently demoted to replicated (keeps decode S=1 / batch=1
    cells valid without per-call branching).
    """
    rules = current_rules()
    if rules is None:
        return x
    assert len(axes) == x.ndim, (axes, x.shape)
    mesh_axes = []
    for dim, name in enumerate(axes):
        ax = rules.act_axis(name)
        if ax is not None:
            size = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                size *= rules.mesh.shape[a]
            if x.shape[dim] % size != 0:
                ax = None
        mesh_axes.append(ax)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, P(*mesh_axes))
    )


# ---------------------------------------------------------------------------
# Parameter sharding by leaf path
# ---------------------------------------------------------------------------

# name -> logical axes per trailing dims (leading stacked 'groups' dims get
# None). Convention: weights store (in_features, out_features...) with
# named structure below (see models/*.py init functions).
_PARAM_AXES: dict[str, tuple[str | None, ...]] = {
    # embeddings
    "embedding": ("vocab", "embed"),
    "head": ("vocab", "embed"),
    "patch_proj": (None, "embed"),
    # attention
    "wq": ("embed", "heads", None),
    "wk": ("embed", "kv_heads", None),
    "wv": ("embed", "kv_heads", None),
    "wo": ("heads", None, "embed"),
    # dense mlp
    "w_gate": ("embed", "mlp"),
    "w_up": ("embed", "mlp"),
    "w_down": ("mlp", "embed"),
    # moe
    "router": ("embed", None),
    "we_gate": ("experts", "embed", "expert_mlp"),
    "we_up": ("experts", "embed", "expert_mlp"),
    "we_down": ("experts", "expert_mlp", "embed"),
    # rglru
    "w_x": ("embed", "lru"),
    "w_gate_branch": ("embed", "lru"),
    "w_out": ("lru", "embed"),
    "a_param": ("lru",),
    "w_input_gate": ("lru_in", "lru"),
    "w_rec_gate": ("lru_in", "lru"),
    "conv_w": (None, "lru"),
    "conv_b": ("lru",),
    # mlstm
    "w_m_up": ("embed", "mlstm_inner"),
    "w_m_z": ("embed", "mlstm_inner"),
    "w_m_q": ("mlstm_in", None, None),
    "w_m_k": ("mlstm_in", None, None),
    "w_m_v": ("mlstm_in", "m_heads", "m_vdim"),
    "w_m_gates": ("mlstm_in", None),
    "w_m_down": ("mlstm_inner", "embed"),
    # slstm
    "w_s_in": ("embed", "slstm_units"),
    "r_s": (None, None, "slstm_units"),
    "b_s": ("slstm_units",),
    # norms / biases / scalars
    "scale": (None,),
    "bias": (None,),
    "b_gates": (None,),
}

# logical param axis -> (tp_axis?, dp?) mapping
def _param_axis_to_mesh(rules: ShardingRules, name: str | None):
    if name is None:
        return None
    tp, dp = rules.tp_axis, (rules.dp if rules.zero3 else None)
    table = {
        "vocab": tp,
        "embed": dp,             # ZeRO-3 dim
        "heads": tp,
        "kv_heads": tp,          # auto-replicated when KV < tp (guard below)
        "mlp": tp,
        "expert_mlp": None,
        "experts": tp,           # EP over the TP axis (E >= tp archs)
        "lru": tp,
        "lru_in": None,
        "mlstm_inner": tp,
        "mlstm_in": None,
        "m_heads": None,
        "m_vdim": tp,
        "slstm_units": tp,
    }
    if rules.strategy == "fsdp":
        # weights fully sharded over dp x tp on the embed/input dim,
        # gathered whole per layer; no feature-dim TP
        fsdp_dim = (rules.dp_axes + (tp,)) if rules.zero3 else (tp,)
        table.update(
            vocab=None, embed=fsdp_dim, heads=None, kv_heads=None,
            mlp=None,
        )
    return table[name]


def _path_names(path: tuple) -> list[str]:
    """All string components of a pytree path (DictKey `.key` AND
    GetAttrKey `.name` — dataclass fields like ``sketch``/``nodes``
    only show up through the latter)."""
    names = []
    for part in path:
        key = getattr(part, "key", None)
        if not isinstance(key, str):
            key = getattr(part, "name", None)
        if isinstance(key, str):
            names.append(key)
    return names


def _sketch_path_info(path: tuple):
    """(node_name, leaf_name) when `path` addresses NodeTree sketch
    state, else None. Triples/psi live at ...nodes/<name>/{x,y,z,psi};
    the shared projections at ...proj/{upsilon,omega,phi}."""
    names = _path_names(path)
    if not names:
        return None
    leaf = names[-1]
    if leaf in ("x", "y", "z", "psi") and "nodes" in names:
        i = len(names) - 1 - names[::-1].index("nodes")   # last "nodes"
        if i == len(names) - 3:       # .../nodes/<node_name>/<leaf>
            return (names[i + 1], leaf)
        return None
    if leaf in ("upsilon", "omega", "phi") and "proj" in names:
        return (None, leaf)
    if leaf == "params" and "proj" in names:
        # psparse trees: the only projection leaf is the (3, 4) uint32
        # hash-coefficient array — O(1) bytes, always replicated
        return (None, leaf)
    return None


def spec_for_sketch(rules: ShardingRules, node_name: str | None,
                    leaf_name: str, leaf) -> P:
    """PartitionSpec for one sketch leaf (DESIGN.md §12).

    A node's (…, d, k) triple shards its WIDTH dim exactly as the
    consumer weight shards that same feature dim: the node's logical
    axis ("embed" | "mlp" | "heads", from the DEFAULT_NODE_AXES
    registry — ShapeDtypeStructs can't carry the SketchNode annotation)
    resolves through `_param_axis_to_mesh`, then the ZeRO dp axes are
    appended so replicated sketch state never scales with d. Members
    are dropped back-to-front when d doesn't divide (TP alignment with
    the weight survives longest). psi is k-sized — replicated. The
    shared (T, k) projections shard token rows over dp."""
    shape = leaf.shape
    ndim = leaf.ndim if hasattr(leaf, "ndim") else len(shape)
    if leaf_name in ("psi", "params"):
        return P()
    if leaf_name in ("upsilon", "omega", "phi"):
        if ndim != 2 or shape[0] % rules.dp_size != 0:
            return P()
        return P(rules.dp, None)
    from repro.sketches.node import DEFAULT_NODE_AXES, \
        DEFAULT_NODE_STACK_AXES
    logical = DEFAULT_NODE_AXES.get(node_name)
    ax = _param_axis_to_mesh(rules, logical)
    members = list(ax) if isinstance(ax, tuple) else \
        ([ax] if ax is not None else [])
    if rules.zero3:
        members += [a for a in rules.dp_axes if a not in members]
    d = shape[-2] if ndim >= 2 else shape[-1]

    def _prod(ms):
        n = 1
        for a in ms:
            n *= rules.mesh.shape[a]
        return n

    # Expert-axis rule (DESIGN.md §15): the TRAILING stack dims of a
    # multi-dim stack shard over their registered logical axes — a
    # per-expert (L, E, d, k) triple shards E over "experts" exactly as
    # its expert's weights do under the shard_map EP layout, so each EP
    # shard owns only its local experts' sketch state.
    n_stack = max(ndim - 2, 0)
    stack_spec: list = [None] * n_stack
    used: list = []
    stack_axes = DEFAULT_NODE_STACK_AXES.get(node_name, ())
    if n_stack and stack_axes:
        take = stack_axes[-n_stack:]
        for j, sname in enumerate(take):
            dim = n_stack - len(take) + j
            s_ax = _param_axis_to_mesh(rules, sname)
            ms = list(s_ax) if isinstance(s_ax, tuple) else \
                ([s_ax] if s_ax is not None else [])
            if ms and shape[dim] % _prod(ms) == 0:
                stack_spec[dim] = tuple(ms) if len(ms) > 1 else ms[0]
                used += ms
    members = [a for a in members if a not in used]
    while members and d % _prod(members) != 0:
        members.pop()
    d_ax = tuple(members) if len(members) > 1 else \
        (members[0] if members else None)
    if ndim < 2:
        return P(d_ax)
    return P(*(stack_spec + [d_ax, None]))


def spec_for_param(rules: ShardingRules, path: tuple, leaf) -> P:
    """PartitionSpec for one param leaf, from its pytree path + shape."""
    sketch = _sketch_path_info(path)
    if sketch is not None:
        return spec_for_sketch(rules, sketch[0], sketch[1], leaf)
    # last DictKey string in the path identifies the weight
    name = None
    for part in reversed(path):
        key = getattr(part, "key", None)
        if isinstance(key, str):
            name = key
            break
    axes = _PARAM_AXES.get(name)
    if axes is None:
        return P()          # unknown -> replicated
    ndim = leaf.ndim if hasattr(leaf, "ndim") else len(leaf.shape)
    lead = ndim - len(axes)  # leading stacked (groups) dims
    mesh_axes = [None] * lead + [
        _param_axis_to_mesh(rules, a) for a in axes
    ]
    # divisibility guard (e.g. E=8 experts on tp=16 -> replicate that dim)
    shape = leaf.shape

    def _size(ax):
        size = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            size *= rules.mesh.shape[a]
        return size

    # a mesh axis may appear in at most one dim: when the fsdp embed
    # tuple collides with a tensor-parallel dim (recurrent/expert
    # weights keep feature-TP), strip the duplicated member(s)
    used: set = set()
    for i, ax in enumerate(mesh_axes):
        if ax is None:
            continue
        members = tuple(ax) if isinstance(ax, tuple) else (ax,)
        if isinstance(ax, tuple):
            kept = tuple(m for m in members if m not in used)
            mesh_axes[i] = kept if len(kept) > 1 else \
                (kept[0] if kept else None)
            members = kept
        elif ax in used:
            mesh_axes[i] = None
            members = ()
        used.update(members)

    for i, ax in enumerate(mesh_axes):
        if ax is not None and shape[i] % _size(ax) != 0:
            mesh_axes[i] = None
    # MoE fallback: when the experts dim cannot shard over tp (E < tp, e.g.
    # mixtral 8e on model=16), switch to tensor-parallel expert FFNs by
    # sharding the expert_mlp dim instead (DESIGN.md §4 EP/TP hybrid).
    if name in ("we_gate", "we_up", "we_down") and mesh_axes[lead] is None:
        j = lead + axes.index("expert_mlp")
        if shape[j] % _size(rules.tp_axis) == 0:
            mesh_axes[j] = rules.tp_axis
    return P(*mesh_axes)


def param_shardings(rules: ShardingRules, params) -> Any:
    """NamedSharding pytree matching `params` (works on ShapeDtypeStructs)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            rules.mesh, spec_for_param(rules, path, leaf)
        ),
        params,
    )


# cache leaf name -> rank WITHOUT the leading stacked-groups dim
_CACHE_RANKS = {
    "k": 4, "v": 4,                     # (B, KV, C, D)
    "C": 4, "m_n": 3, "m_m": 2,         # mLSTM (B,H,Dk,Dv)/(B,H,Dk)/(B,H)
    "conv": 3,                          # (B, W-1, F)
    "s_c": 2, "s_n": 2, "s_m": 2, "s_h": 2,   # sLSTM (B, units)
    "r_h": 2,                           # RG-LRU (B, lru)
}


def cache_shardings(cache, cfg, rules):
    """Decode-cache layout (DESIGN.md §4): batch over dp everywhere;
    attention KV caches head-sharded when KV >= TP, else sequence-sharded
    over the model axis (flash-decoding merge); recurrent states sharded
    on their feature dim."""
    mesh, dp, tp = rules.mesh, rules.dp, rules.tp_axis
    dp_size, tp_size = rules.dp_size, rules.tp_size

    def spec(path, leaf):
        name = None
        for part in reversed(path):
            key = getattr(part, "key", None)
            if isinstance(key, str):
                name = key
                break
        shp = leaf.shape
        axes = [None] * len(shp)
        rank = _CACHE_RANKS.get(name)
        if rank is None or len(shp) < rank:
            return NamedSharding(mesh, P(*axes))
        lead = len(shp) - rank            # 1 when group-stacked, else 0
        b = lead                          # batch dim index
        if shp[b] % dp_size == 0:
            axes[b] = dp
        if name in ("k", "v"):
            if cfg.num_kv_heads >= tp_size and shp[b + 1] % tp_size == 0:
                axes[b + 1] = tp          # kv-head sharded
            elif shp[b + 2] % tp_size == 0:
                axes[b + 2] = tp          # sequence-sharded cache
        elif shp[-1] % tp_size == 0:      # feature dim of recurrent state
            axes[-1] = tp
        return NamedSharding(mesh, P(*axes))

    return jax.tree_util.tree_map_with_path(spec, cache)
