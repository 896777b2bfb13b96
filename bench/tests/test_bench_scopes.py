"""The in-step readers (`bench/scopes.py`) on a small synthetic trace.

`data/scoped_trace.json.gz`: two train-step runs, [1000, 1100) and
[1150, 1250) us. In the first, a `while` op [1000, 1080) encloses an
attention fusion [1005, 1025), a sketch-update one [1030, 1040) and a
reconstruction one [1040, 1060); an AdamW fusion [1080, 1095) and a
copy with no op_name [1095, 1100) follow. In the second, the `while` op
[1150, 1230) encloses attention [1150, 1170), which starts with it, and
a sketch update [1200, 1210); AdamW runs [1235, 1250). A scoped op of
another program runs after the window. The device is idle in
[1100, 1150) and [1230, 1235); the host's `loop/feed` [1125, 1148)
covers 23 us of the first gap, its `loop/sync` spans [1090, 1120) and
[1225, 1260) cover 20 us of the first and 5 us of the second.
"""
import os
import types

import pytest

from bench import scopes, spec, trace

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    tr = trace.Trace.load(os.path.join(HERE, "data", name))
    return types.SimpleNamespace(window_trace=tr,
                                 window=trace.window(tr, "train_step"),
                                 step_module="train_step")


@pytest.fixture
def scoped():
    return load("scoped_trace.json.gz")


@pytest.mark.parametrize("metric,want", [
    # device us in the scope over 2 steps
    ("attention_step_ms", 40 / 2 * 1e-3),
    ("sketch_update_step_ms", 20 / 2 * 1e-3),
    ("reconstruct_step_ms", 20 / 2 * 1e-3),
    ("adamw_step_ms", 30 / 2 * 1e-3),
    # idle us under the span over the 250 us window
    ("feed_idle_share", 100 * 23 / 250),
    ("sync_idle_share", 100 * (20 + 5) / 250),
])
def test_in_step_readers(scoped, metric, want):
    assert spec.metric_module(metric).read(scoped) == pytest.approx(want)


def test_scopes_and_other_sum_to_busy_time(scoped):
    tr, (lo, hi) = scoped.window_trace, scoped.window
    by = scopes.device_us_by_scope(tr, lo, hi)
    # the while ops keep only the time between their body's ops, and
    # the copy with no op_name falls to other: 30 + 5 + 50 us
    assert by == pytest.approx({"attention": 40.0, "sketch_update": 20.0,
                                "reconstruct": 20.0, "adamw": 30.0,
                                "other": 85.0})
    assert sum(by.values()) == pytest.approx(trace.busy_us(tr, lo, hi))


def test_idle_under_spans_within_device_idle(scoped):
    idle = spec.metric_module("device_idle_share").read(scoped)
    assert idle == pytest.approx(100 * 55 / 250)
    assert scopes.idle_share_under(scoped, scopes.FEED_SPAN) + \
        scopes.idle_share_under(scoped, scopes.SYNC_SPAN) <= idle


@pytest.mark.parametrize("metric", [
    "attention_step_ms", "adamw_step_ms", "sketch_update_step_ms",
    "reconstruct_step_ms", "feed_idle_share", "sync_idle_share"])
def test_in_step_readers_find_nothing_without_names(metric):
    # a program with no scopes and no loop spans, and no trace at all
    assert spec.metric_module(metric).read(
        load("small_trace.json.gz")) is None
    assert spec.metric_module(metric).read(
        types.SimpleNamespace(window_trace=None, window=None)) is None


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/jvp()/while/body/closed_call/attention/dot:",
     "attention"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/"
     "reconstruct/jit(qr)/householder_product:", "reconstruct"),
    ("jit(train_step)/adamw", "adamw"),
    ("jit(train_step)/adamw:fusion", "adamw"),
    ("jit(train_step)/attention_like/mul:", "other"),
    ("jit(attention)/mul:", "other"),
    ("", "other"),
])
def test_scope_of(op_name, want):
    assert scopes.scope_of(op_name) == want


def test_names_are_the_programs():
    from repro import telemetry

    assert scopes.SCOPES == telemetry.SCOPES
    assert {scopes.FEED_SPAN, scopes.SYNC_SPAN} <= set(telemetry.LOOP_SPANS)
