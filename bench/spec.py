"""The benchmark's data, found by name.

`BENCHMARK.json` at the repo root lists the cells; each cell names a
configuration (`bench/configs/<config>.json`), a traffic mix
(`bench/traffic/<traffic>.json`) and, through its metrics, one reader
per per-layer metric (`bench/metrics/<name>.py`). The limits of the
comparison that decides `correct` are `bench/limits/<cell>.json`. A new
cell, configuration or metric is new files plus entries: nothing here
names one.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple          # metric entries this cell reports
    per_layer: tuple


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    bm = benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=load_json(root / cfg_entry["file"]),
        traffic=load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH_DIR / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in bm["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in bm["per_layer"] if _reports(m, name)),
    )


def metric_module(name: str):
    """The reader of one per-layer metric: bench/metrics/<name>.py."""
    return importlib.import_module(f"bench.metrics.{name}")


# --------------------------------------------------------------------------
# what the cell's files say, as the program and the reference take it
# --------------------------------------------------------------------------


def arch_config(c: Cell):
    """The program's ArchConfig: the registry's entry with every field
    the configuration file gives, checked against the source keys."""
    import jax.numpy as jnp

    from repro.configs import get_arch

    model = dict(c.config["model"])
    for field, key in c.config["model_keys"].items():
        if model[field] != c.config["config"][key]:
            raise ValueError(f"{c.config_name}: model.{field}="
                             f"{model[field]} but config.{key}="
                             f"{c.config['config'][key]}")
    for k in ("dtype", "param_dtype"):
        model[k] = jnp.dtype(model[k]).type
    model["remat_policy"] = c.traffic["remat"]
    return dataclasses.replace(get_arch(c.config["registry"]), **model)


def run_config(c: Cell):
    """The program's RunConfig for this traffic mix."""
    from repro.models.transformer import SketchSettings
    from repro.optim.adamw import AdamWConfig
    from repro.train.state import RunConfig

    t = c.traffic
    o = t["optimizer"]
    return RunConfig(
        seq_len=t["seq_len"], global_batch=t["batch"],
        optimizer=AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"],
                              eps=o["eps"],
                              weight_decay=o["weight_decay"],
                              grad_clip=o["grad_clip"]),
        warmup_steps=t["warmup_steps"], total_steps=t["total_steps"],
        z_weight=t["z_weight"],
        sketch=SketchSettings(enabled=t["sketch"], k_max=t["k_max"],
                              beta=t["beta"], recon_mode=t["recon_mode"],
                              ridge=t["ridge"], factored=t["factored"],
                              proj_kind=t["proj_kind"]))


def reference_spec(c: Cell):
    """The reference's Spec, from the same two files."""
    from bench.reference import Spec

    m, t, o = c.config["model"], c.traffic, c.traffic["optimizer"]
    if t["recon_mode"] != "fast" or t["proj_kind"] != "gaussian" or \
            m["tie_embeddings"]:
        raise ValueError("the reference has a separate output head and "
                         "reconstructs in 'fast' mode from Gaussian "
                         "projections only")
    return Spec(
        d=m["d_model"], heads=m["num_heads"], kv_heads=m["num_kv_heads"],
        head_dim=m["head_dim"], d_ff=m["d_ff"], vocab=m["vocab_size"],
        layers=m["num_layers"], mlp=m["mlp_type"], norm_eps=m["norm_eps"],
        rope_theta=m["rope_theta"], seq=t["seq_len"], batch=t["batch"],
        sketch=t["sketch"], k=t["k_max"], beta=t["beta"],
        ridge=t["ridge"], lr=o["lr"], b1=o["b1"], b2=o["b2"],
        adam_eps=o["eps"], weight_decay=o["weight_decay"],
        grad_clip=o["grad_clip"], warmup_steps=t["warmup_steps"],
        total_steps=t["total_steps"], z_weight=t["z_weight"])
