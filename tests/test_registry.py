"""NodeSpec registry + RunConfig compatibility matrix (DESIGN.md §15).

Covers the ISSUE 10 API surface: `node_specs_for` as the single spec-
resolution path (grep-asserted below), the deprecated shim names, the
expert-axis sharding rule for multi-dim node stacks, the structured
`ConfigError` matrix, and the legacy-checkpoint rejection of
post-legacy node kinds.
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


# ---------------------------------------------------------------------------
# registry dispatch
# ---------------------------------------------------------------------------

def _arch(name):
    from repro.configs import get_arch, reduced
    return reduced(get_arch(name))


@pytest.mark.parametrize("arch,family,expected", [
    ("tinyllama-1.1b", "lm",
     {"ffn_in": (64, 2), "ffn_h": (128, 2)}),
    ("qwen3-moe-30b-a3b", "moe",
     {"attn_o": (64, 2), "expert_in": (64, (2, 4))}),
    ("xlstm-1.3b", "recurrent",
     {"res": (64, 8), "mlstm_c": (2048, 7), "mlstm_n": (64, 7)}),
    ("recurrentgemma-2b", "recurrent",
     {"ffn_in": (64, 3), "ffn_h": (128, 3), "rglru_h": (64, 2)}),
])
def test_node_specs_for_arch_families(arch, family, expected):
    from repro.sketches.registry import family_for, node_specs_for

    cfg = _arch(arch)
    assert family_for(cfg) == family
    specs = node_specs_for(cfg)
    assert {n: (s.width, s.layers) for n, s in specs.items()} == expected


def test_node_specs_for_paper_configs():
    from repro.configs.paper import CIFAR_CONV, MNIST_MLP
    from repro.sketches.registry import family_for, node_specs_for

    assert family_for(MNIST_MLP) == "mlp"
    mlp = node_specs_for(MNIST_MLP)
    assert set(mlp) == {"hidden"} and mlp["hidden"].layers == 3

    assert family_for(CIFAR_CONV) == "conv"
    conv = node_specs_for(CIFAR_CONV)
    # im2col patch widths: 3*3*channels and 3*3*8 (XConv factoring)
    assert {n: s.width for n, s in conv.items()} == \
        {"conv1": 27, "conv2": 72}


def test_family_for_rejects_unknown_config_type():
    from repro.sketches.registry import family_for

    with pytest.raises(TypeError, match="register_node_specs"):
        family_for(object())


def test_register_node_specs_last_wins_and_validates():
    from repro.sketches.registry import (
        _REGISTRY, register_node_specs, registered_families,
    )

    with pytest.raises(ValueError):
        register_node_specs("", lambda cfg: {})
    prev = _REGISTRY.get("mlp")
    try:
        register_node_specs("mlp", lambda cfg, **kw: {"ov": None})
        assert "mlp" in registered_families()
        from repro.configs.paper import MNIST_MLP
        from repro.sketches.registry import node_specs_for
        assert node_specs_for(MNIST_MLP) == {"ov": None}
    finally:
        _REGISTRY["mlp"] = prev


def test_deprecated_spec_shims_warn_and_match_registry():
    from repro.configs.paper import MNIST_MLP
    from repro.models.mlp import mlp_node_specs
    from repro.models.transformer import lm_node_specs
    from repro.sketches.registry import node_specs_for

    cfg = _arch("tinyllama-1.1b")
    with pytest.warns(DeprecationWarning):
        old = lm_node_specs(cfg)
    assert old == node_specs_for(cfg)
    with pytest.warns(DeprecationWarning):
        old = mlp_node_specs(MNIST_MLP)
    assert old == node_specs_for(MNIST_MLP)


def test_launch_reaches_specs_only_via_node_specs_for():
    """Acceptance criterion: `node_specs_for` is the only spec-
    resolution path reachable from launch/ — no module on the
    launch->train->serve import cone may name the per-family spec
    functions directly."""
    banned = ("lm_node_specs", "mlp_node_specs", "transformer_node_specs",
              "_mlp_node_specs")
    offenders = []
    for sub in ("launch", "train", "serve", "telemetry"):
        d = SRC / sub
        if not d.exists():
            continue
        for f in sorted(d.rglob("*.py")):
            text = f.read_text()
            offenders += [(f.name, b) for b in banned if b in text]
    assert not offenders, offenders
    # and the spec-consuming entry points DO go through the registry
    assert "node_specs_for" in (SRC / "models" / "transformer.py").read_text()
    assert "node_specs_for" in (SRC / "train" / "paper_trainer.py").read_text()


# ---------------------------------------------------------------------------
# expert-axis sharding rule (multi-dim node stacks)
# ---------------------------------------------------------------------------

def test_spec_for_sketch_shards_expert_axis():
    from repro.launch.mesh import make_mesh, rules_for_mesh
    from repro.parallel.sharding import spec_for_sketch
    from jax.sharding import PartitionSpec as P

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    rules = rules_for_mesh(make_mesh(2, 4))
    x = jax.ShapeDtypeStruct((2, 4, 64, 9), jnp.float32)
    # (L, E, d, k): E shards over the TP ("model") axis like the expert
    # weights, d keeps the ZeRO dp dim, k replicated
    assert spec_for_sketch(rules, "expert_in", "x", x) == \
        P(None, "model", "data", None)
    # psi is k-sized — always replicated
    psi = jax.ShapeDtypeStruct((2, 4, 9), jnp.float32)
    assert spec_for_sketch(rules, "expert_in", "psi", psi) == P()
    # an E that doesn't divide tp drops the expert member, keeps dp on d
    x3 = jax.ShapeDtypeStruct((2, 3, 64, 9), jnp.float32)
    assert spec_for_sketch(rules, "expert_in", "x", x3) == \
        P(None, None, "data", None)


def test_node_paths_and_monitor_rows_cover_expert_stacks():
    from repro.sketches import init_node_tree, node_paths
    from repro.sketches.tree import NodeSpec
    from repro.core.monitor import tree_metrics

    tree = init_node_tree(
        jax.random.PRNGKey(0),
        {"expert_in": NodeSpec(width=8, layers=(2, 3), kind="paper"),
         "ffn_in": NodeSpec(width=8, layers=2, kind="paper")},
        num_tokens=16, k_max=5)
    paths = node_paths(tree)
    assert len(paths) == 2 * 3 + 2
    # one metrics row per stack entry, (L, E) flattened row-major
    assert tree_metrics(tree).shape[0] == len(paths)


# ---------------------------------------------------------------------------
# RunConfig compatibility matrix
# ---------------------------------------------------------------------------

def _run_cfg(**kw):
    from repro.models.transformer import SketchSettings
    from repro.train.state import RunConfig

    base = dict(seq_len=16, global_batch=8,
                sketch=SketchSettings(enabled=True, k_max=9))
    base.update(kw)
    return RunConfig(**base)


@pytest.mark.parametrize("kw,fields", [
    (dict(dp_axis_name=("pod", "data"), dp_workers=4, ring_wire=True),
     ("ring_wire", "dp_axis_name")),
    (dict(dp_axis_name="data", dp_workers=4, ring_wire=True,
          dp_collective="per_node"), ("ring_wire", "dp_collective")),
    (dict(dp_axis_name="data", dp_workers=4, sketch_wire_dtype="int8",
          dp_collective="per_node"),
     ("sketch_wire_dtype", "dp_collective")),
    (dict(sketch_wire_dtype="int8"), ("sketch_wire_dtype", "dp_axis_name")),
    (dict(dp_axis_name="data", dp_workers=4, dp_merge="reduce_scatter",
          dp_collective="per_node"), ("dp_merge", "dp_collective")),
    (dict(dp_merge="reduce_scatter"), ("dp_merge", "dp_axis_name")),
    (dict(dp_axis_name="data", dp_workers=3), ("global_batch", "dp_workers")),
])
def test_run_config_matrix_rejects_at_construction(kw, fields):
    from repro.train.state import ConfigError

    with pytest.raises(ConfigError) as ei:
        _run_cfg(**kw)
    assert ei.value.fields == fields
    # the structured message names both conflicting fields
    assert all(f in str(ei.value) for f in fields)


def test_run_config_matrix_accepts_valid_combinations():
    # every flag family at a valid setting composes
    _run_cfg(dp_axis_name="data", dp_workers=4, dp_collective="overlap",
             dp_merge="reduce_scatter")
    _run_cfg(dp_axis_name="data", dp_workers=4, dp_collective="fused",
             sketch_wire_dtype="int8", ring_wire=True)
    _run_cfg(dp_axis_name=("pod", "data"), dp_workers=4,
             dp_collective="fused")


def test_run_config_consumed_row_raised_by_make_train_step():
    """The one arch-dependent matrix row: reduce_scatter under a
    sketched-BACKPROP (consumed) tree needs the overlap schedule —
    re-checked by make_train_step with the resolved arch fact, raising
    the same structured ConfigError."""
    from repro.configs import get_arch, reduced
    from repro.train.state import ConfigError
    from repro.train.step import make_train_step

    run = _run_cfg(dp_axis_name="data", dp_workers=4,
                   dp_collective="fused", dp_merge="reduce_scatter")
    run.validate()  # construction-legal: monitor-only trees allow it
    cfg = reduced(get_arch("tinyllama-1.1b"))  # ffn nodes => consumed
    with pytest.raises(ConfigError) as ei:
        make_train_step(cfg, run)
    assert ei.value.fields == ("dp_merge", "dp_collective")


def test_launch_cli_reports_config_error(monkeypatch, capsys):
    import sys
    from repro.launch.train import main

    monkeypatch.setattr(sys, "argv", [
        "train", "--arch", "tinyllama-1.1b", "--reduced",
        "--dp", "2", "--dp-collective", "per_node",
        "--dp-merge", "reduce_scatter", "--steps", "1"])
    with pytest.raises(SystemExit, match="invalid flag combination"):
        main()


# ---------------------------------------------------------------------------
# legacy-checkpoint compat: unknown node kinds rejected clearly
# ---------------------------------------------------------------------------

def test_compat_rejects_post_legacy_node_names():
    from repro.sketches import init_node_tree
    from repro.sketches.compat import adopt_legacy, legacy_layout
    from repro.sketches.tree import NodeSpec

    tree = init_node_tree(
        jax.random.PRNGKey(0),
        {"expert_in": NodeSpec(width=8, layers=(2, 3), kind="paper")},
        num_tokens=16, k_max=5)
    with pytest.raises(ValueError, match="expert_in.*postdate|postdate"):
        legacy_layout(tree)
    with pytest.raises(ValueError, match="postdate"):
        adopt_legacy({}, tree)


def test_compat_adopt_reports_missing_nodes():
    from repro.sketches import init_node_tree
    from repro.sketches.compat import adopt_legacy, legacy_layout
    from repro.sketches.tree import NodeSpec

    specs = {"ffn_in": NodeSpec(width=8, layers=2, kind="paper"),
             "ffn_h": NodeSpec(width=12, layers=2, kind="paper")}
    tree = init_node_tree(jax.random.PRNGKey(0), specs, 16, 5)
    legacy = legacy_layout(tree)
    del legacy["ffn_h"]
    with pytest.raises(ValueError, match="ffn_h"):
        adopt_legacy(legacy, tree)
