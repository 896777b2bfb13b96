"""The program's instrumentation (DESIGN.md §11): named scopes in the
lowered train step, the loop's host spans and per-step compile count in
its history and telemetry, the spans on the profiler's timeline, and
the process-wide compile counter."""
import glob
import gzip
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch, reduced
from repro.models.transformer import SketchSettings
from repro.telemetry import (
    LOOP_SPANS, SCOPES, compile_counter, read_jsonl, scope, span,
)
from repro.train.state import RunConfig, init_train_state


def _run(sketch: bool, steps: int = 4):
    return RunConfig(global_batch=2, seq_len=16, total_steps=steps,
                     warmup_steps=1,
                     sketch=SketchSettings(enabled=sketch, k_max=9))


def _op_name_parts(text: str) -> set:
    return {part for loc in re.findall(r'loc\("([^"]*)"', text)
            for part in loc.split("/")}


@pytest.mark.parametrize("sketch,present,absent", [
    (True, SCOPES, ()),
    (False, ("attention", "adamw"), ("sketch_update", "reconstruct")),
])
def test_train_step_carries_the_scopes(sketch, present, absent):
    """Lowered as the loop lowers it (state donated): every scope the
    config runs is a component of some op's name, and no other."""
    from repro.train.step import make_train_step

    cfg = reduced(get_arch("tinyllama-1.1b"))
    run = _run(sketch)
    state = jax.eval_shape(lambda k: init_train_state(k, cfg, run),
                           jax.random.PRNGKey(0))
    batch = {n: jax.ShapeDtypeStruct((2, 16), jnp.int32)
             for n in ("tokens", "labels")}
    step = jax.jit(make_train_step(cfg, run), donate_argnums=(0,))
    parts = _op_name_parts(step.lower(state, batch).as_text(
        debug_info=True))
    assert set(present) <= parts
    assert not set(absent) & parts


def test_scope_names_are_checked():
    with pytest.raises(ValueError, match="unknown scope"):
        scope("attn")


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from repro.train.loop import LoopConfig, run_training

    d = tmp_path_factory.mktemp("instrument")
    path = str(d / "train.jsonl")
    loop = LoopConfig(num_steps=3, ckpt_every=2, log_every=1,
                      ckpt_dir=str(d / "ck"), telemetry_path=path)
    _, hist = run_training(reduced(get_arch("tinyllama-1.1b")),
                           _run(True), loop, seed=0)
    return hist, read_jsonl(path)[1]


def test_loop_fills_spans_and_compiles(trained):
    hist, recs = trained
    assert [h["step"] for h in hist] == [r.step for r in recs] == [0, 1, 2]
    for h, r in zip(hist, recs):
        assert set(h["spans"]) == set(LOOP_SPANS) | {"step"}
        assert all(v >= 0 for v in h["spans"].values())
        assert h["spans"]["step"] == h["time_s"] == pytest.approx(
            h["spans"]["loop/dispatch"] + h["spans"]["loop/sync"])
        # the record, written once the step ended, has all its spans
        assert r.spans == h["spans"]
        assert r.scalars["compiles"] == h["compiles"]
    # the first step lowers the train step; the later ones lower nothing
    assert hist[0]["compiles"] >= 1
    assert [h["compiles"] for h in hist[1:]] == [0, 0]


def test_compile_counter_counts_a_forced_lowering():
    c = compile_counter()
    assert compile_counter() is c
    f = jax.jit(lambda x: x * 3 + 1)
    x3, x5 = jnp.ones(3), jnp.ones(5)
    n = c.lowerings
    f(x3)
    f(x3)
    assert c.lowerings == n + 1
    f(x5)                            # a new shape
    assert c.lowerings == n + 2
    jax.clear_caches()               # the same call, lowered again
    f(x3)
    assert c.lowerings == n + 3


def test_span_reaches_the_profiler_timeline(tmp_path):
    spans = {}
    jax.profiler.start_trace(str(tmp_path), create_perfetto_trace=True)
    try:
        with span(spans, "loop/feed") as block:
            block(jnp.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    assert spans["loop/feed"] > 0
    path, = glob.glob(os.path.join(str(tmp_path), "**",
                                   "perfetto_trace.json.gz"),
                      recursive=True)
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    assert any(e.get("ph") == "X" and e.get("name") == "loop/feed"
               for e in events)
