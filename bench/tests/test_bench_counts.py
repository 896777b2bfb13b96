"""The benchmark's own counts: model operations per step, the roofline
counts, and the trace reduction on a small recorded trace."""
import os

import pytest

from bench import flops, spec, trace

HERE = os.path.dirname(os.path.abspath(__file__))


def model(cell):
    c = spec.cell(cell)
    return c.config["model"], c.traffic


@pytest.mark.parametrize("cell,want", [
    # 6 x 619 M matmul weights x 2048 tokens + causal attention
    ("stablelm-12b.plain-s2048", 7.87e12),
    # the MLP linears' dense weight gradients replaced by rank-17 products
    ("stablelm-12b.sketch-s2048", 6.15e12),
])
def test_train_step_flops(cell, want):
    m, t = model(cell)
    assert flops.train_step(m, t)["flops"] == pytest.approx(want, rel=0.01)


def test_sketch_saves_dense_weight_gradients():
    for cfg in ("stablelm-12b", "granite-34b"):
        cells = {c.traffic_name: c for c in map(spec.cell, [
            w["name"] for w in spec.benchmark()["workloads"]
            if w["config"] == cfg])}
        plain = [c for n, c in cells.items() if n.startswith("plain")][0]
        sk = [c for n, c in cells.items() if n.startswith("sketch")][0]
        p = flops.train_step(plain.config["model"], plain.traffic)["flops"]
        s = flops.train_step(sk.config["model"], sk.traffic)["flops"]
        m, t = sk.config["model"], sk.traffic
        T = t["batch"] * t["seq_len"]
        dense = sum(2 * T * a * b for n, (a, b) in
                    flops.matmul_params(m).items() if n.startswith("w_"))
        assert p - s == pytest.approx(dense * m["num_layers"], rel=0.02)


def test_attention_and_adamw_counts():
    a = flops.attention(1, 2048, 32, 8, 160)
    assert a["flops"] == 3 * 2 * 2048 * 2048 * 32 * 160
    assert flops.adamw(10)["bytes"] == 320
    s = flops.sketch_update(2048, 5120, 17)
    assert s["flops"] == 6 * 2048 * 5120 * 17


@pytest.fixture
def small():
    return trace.Trace.load(os.path.join(HERE, "data",
                                         "small_trace.json.gz"))


def test_trace_busy_idle_and_window(small):
    assert small.device_pids() and small.host_pids()
    lo, hi = trace.window(small, "train_step")
    assert (lo, hi) == (1000.0, 1220.0)
    # ops [1000,1040) [1030,1040) [1050,1100) [1120,1220): union 190 us
    assert trace.busy_us(small, lo, hi) == pytest.approx(190.0)
    assert trace.gaps_us(small, lo, hi) == [(1040.0, 1050.0),
                                           (1100.0, 1120.0)]


def test_trace_op_times_and_gap_names(small):
    ops = trace.op_times_us(small, 1000.0, 1220.0)
    assert ops == {"fusion.1": 140.0, "copy.2": 10.0,
                   "convolution.3": 50.0}
    b = trace.breakdown(small, 1000.0, 1220.0)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(140e-6)]
    # the longer gap falls in the host's feed; the other only in the loop
    assert b["idle_gaps"][0] == ["host_batch", pytest.approx(20e-6)]
    assert b["idle_gaps"][1] == ["run_training", pytest.approx(10e-6)]
    assert trace.module_runs_us(small, "jit_bench_adamw") == [30.0]


def test_window_readers_on_the_small_trace(small):
    import types

    from bench import spec as sp

    ctx = types.SimpleNamespace(
        window_trace=small, window=trace.window(small, "train_step"),
        step_module="train_step", flops_per_step=197e12 * 60e-6,
        device_kind="TPU v5 lite")
    idle = sp.metric_module("device_idle_share").read(ctx)
    assert idle == pytest.approx(100 * (1 - 190 / 220))
    # one step period of 120 us holding 60 us of peak-rate work
    assert sp.metric_module("mfu").read(ctx) == pytest.approx(50.0)
    empty = types.SimpleNamespace(window_trace=None, window=None)
    assert sp.metric_module("device_idle_share").read(empty) is None
    assert sp.metric_module("mfu").read(empty) is None
