"""Every entry of BENCHMARK.json resolves to its files, and every name,
unit and text keeps to the characters the benchmark allows."""
import importlib
import json
import re

import pytest

from bench import flops, peaks, spec

BM = spec.benchmark()
CELLS = [w["name"] for w in BM["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH_KEYS = {"hidden_size", "intermediate_size", "n_embd", "n_inner",
              "head_dim", "num_experts_per_tok", "kv_lora_rank",
              "q_lora_rank", "state_size", "expand"}


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51
    assert all(not w.startswith("/") and ".." not in w
               for w in BM["command"])


def test_names_units_and_text():
    names = [c["name"] for c in BM["configs"]] + CELLS + \
        [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in BM["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 0 < len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in BM["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not k.endswith(("_dim", "_rank"))
            assert k not in WIDTH_KEYS, k


def test_bounds():
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    setup = [m for m in BM["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    c = spec.cell(name)
    assert c.config["source"] and c.traffic["seq_len"] > 0
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        mod = spec.metric_module(m["name"])
        assert callable(mod.read)
        assert m["moves"] in {e["name"] for e in c.end_to_end}
    want = {"batch_mismatch", "loss_gap", "grad_norm_gap",
            "first_grad_gap", "change_gap"}
    if c.traffic["sketch"]:
        want |= {"sketched_grad_gap", "first_grad_diff"}
    assert set(c.limits) == want
    assert c.limits["batch_mismatch"] == 0


@pytest.mark.parametrize("name", [c["name"] for c in BM["configs"]])
def test_config_file_matches_registry(name):
    entry = {c["name"]: c for c in BM["configs"]}[name]
    cfg = spec.load_json(spec.ROOT / entry["file"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert set(cfg["published"]) == set(cfg["reduced"])
    for k in cfg["reduced"]:
        assert cfg["config"][k] != cfg["published"][k]
    cell = next(w["name"] for w in BM["workloads"] if w["config"] == name)
    arch = spec.arch_config(spec.cell(cell))
    m = cfg["model"]
    assert arch.d_model == m["d_model"] and arch.d_ff == m["d_ff"]
    assert arch.num_layers == m["num_layers"]
    assert arch.resolved_head_dim == m["head_dim"]
    import jax

    from repro.models.transformer import init_params
    tree = jax.eval_shape(lambda k: init_params(k, arch),
                          jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(tree))
    assert flops.param_count(m) == n
    # the registry's analytic count leaves out the final norm's scale
    assert n == arch.param_count() + m["d_model"]


def test_metric_files_are_named_metrics():
    names = {m["name"] for m in BM["per_layer"]}
    for n in names:
        importlib.import_module(f"bench.metrics.{n}")


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
    # an elementwise pass is bound by bytes, a large matmul by operations
    assert peaks.least_seconds(1e9, 819e9, "TPU v5 lite") == 1.0
    assert peaks.least_seconds(197e12, 1.0, "TPU v5 lite") == 1.0


def test_bench_json_is_small():
    assert len(json.dumps(BM)) < 64 * 1024


def _run(cwd, *args):
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(str(spec.ROOT), "--workload", CELLS[0], "--seed",
             str(2 ** 31 + 3), "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    import shutil

    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), "--workload", CELLS[0], "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_loop_still_has_the_locals_the_feed_reads():
    import repro.train.loop as loop
    from bench.train_cell import LOOP_LOCALS

    code = loop.run_training.__code__
    local = set(code.co_varnames) | set(code.co_cellvars)
    assert set(LOOP_LOCALS) <= local, sorted(local)
    assert "host_batch" in code.co_names


def test_feed_names_the_loop_when_its_locals_are_missing():
    from bench.train_cell import CompileCounter, Feed, GcPauses, StepMarks

    feed = Feed(lambda pipe, step, host: ("tokens", "labels"), check=3,
                settle=3, seconds=1.0, b1=0.9, n_groups=1,
                marks=StepMarks(CompileCounter()), gc_pauses=GcPauses())

    def not_the_loop(pipe, step):
        return feed(pipe, step)
    with pytest.raises(RuntimeError, match="src/repro/train/loop.py"):
        not_the_loop(None, 0)
