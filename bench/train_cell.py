"""A training cell: the program's own loop, driven from the seed.

`repro.train.loop.run_training` builds the state and the jitted step
once. Its first steps are the ones the reference follows; the steps
after them are the measured window; the same objects run both. The
harness stands in for the loop's data feed (`host_batch`): it hands the
program's own batch on unchanged and, at that boundary, reads the state
the previous step left (the loop's local `state`), marks the window and
closes it by raising `WindowClosed` out of the loop once `seconds` have
passed. The host clock is marked at each step's log record.

The loop offers no step hook, so the feed reads the locals named in
`LOOP_LOCALS` from `run_training`'s frame (`src/repro/train/loop.py`);
a test pins those names.
"""
from __future__ import annotations

import dataclasses
import gc
import logging
import sys
import tempfile
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.compare import sample_index


# what the feed reads from `repro.train.loop.run_training`'s frame
LOOP_LOCALS = ("state", "train_step")


class WindowClosed(Exception):
    """Raised from the feed to end the loop once the window is full."""


class CompileCounter:
    """Counts programs lowered for compilation (one per jit cache miss,
    whether or not the persistent cache then serves the executable) and
    sums backend compile seconds."""

    def __init__(self):
        self.lowerings = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, name, secs, **_):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowerings += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs


@dataclasses.dataclass
class Mark:
    step: int
    t: float
    lowerings: int
    loss: float
    grad_norm: float


class StepMarks(logging.Handler):
    """Marks the host clock and the compile count at each step's log
    record ("step %d loss %.4f grad_norm %.3f (%.3fs)")."""

    def __init__(self, counter: CompileCounter):
        super().__init__(logging.INFO)
        self.counter = counter
        self.marks: list[Mark] = []

    def emit(self, record):
        if isinstance(record.msg, str) and record.msg.startswith("step ") \
                and len(record.args) >= 3:
            self.marks.append(Mark(record.args[0], time.perf_counter(),
                                   self.counter.lowerings,
                                   float(record.args[1]),
                                   float(record.args[2])))


class GcPauses:
    """Python's collections, each as (generation, seconds): those of
    set-up in `before`, those after `collect_and_freeze` in `pauses`.
    That call makes one full collection at the end of set-up and freezes
    the set-up's objects out of later collections."""

    def __init__(self):
        self.on = False
        self.before: list[tuple[int, float]] = []
        self.pauses: list[tuple[int, float]] = []
        self.setup_s = 0.0
        self.setup_freed = 0
        self._t = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            (self.pauses if self.on else self.before).append(
                (info["generation"], time.perf_counter() - self._t))

    def collect_and_freeze(self):
        t = time.perf_counter()
        self.setup_freed = gc.collect()
        gc.freeze()
        self.setup_s = time.perf_counter() - t
        self.on = True


# --------------------------------------------------------------------------
# the program's parameters, leaf by leaf
# --------------------------------------------------------------------------


def _key(k) -> str:
    return str(getattr(k, "key", getattr(k, "idx", k)))


def leaf_names(params) -> list[tuple[str, str | None]]:
    """[(path, layer slicing)] of the program's parameter tree in flatten
    order. Group leaves stack their layers on axis 0 ("groups/<pos>/...",
    layer = stack index * positions + pos); tail leaves are one layer."""
    out = []
    n_pos = len(params.get("groups", []))
    for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = [_key(k) for k in path]
        out.append(("/".join(keys), keys[0] if keys[0] in
                    ("groups", "tail") else None))
    return out, n_pos


def _names(path: str, kind: str | None, n: int, n_pos: int,
           n_groups: int) -> list[str]:
    keys = path.split("/")
    if kind == "groups":
        pos = int(keys[1])
        return [f"layer{g * n_pos + pos}/" + "/".join(keys[2:])
                for g in range(n)]
    if kind == "tail":
        return ["layer%d/" % (n_groups * n_pos + int(keys[1]))
                + "/".join(keys[2:])]
    return [path]


@jax.jit
def _norms(tree):
    """Per-leaf norms; group leaves give one norm per stacked layer."""
    def one(path, x):
        x = x.astype(jnp.float32)
        if _key(path[0]) == "groups":
            return jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(
                range(1, x.ndim))))
        return jnp.sqrt(jnp.sum(jnp.square(x)))[None]
    return jax.tree_util.tree_map_with_path(one, tree)


@partial(jax.jit, static_argnums=(2,))
def _diff_norms(a, b, stacked):
    d = jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))
    if stacked:
        return jnp.sqrt(jnp.sum(d, axis=tuple(range(1, d.ndim))))
    return jnp.sqrt(jnp.sum(d))[None]


@jax.jit
def _samples(tree):
    """Per-leaf elements at `sample_index`; group leaves give one row per
    stacked layer."""
    def one(path, x):
        x = x.astype(jnp.float32)
        flat = x.reshape(x.shape[0], -1) if _key(path[0]) == "groups" \
            else x.reshape(1, -1)
        return flat[:, sample_index(flat.shape[1])]
    return jax.tree_util.tree_map_with_path(one, tree)


def named(per_leaf, tree, n_groups: int) -> dict:
    """{reference leaf name: value} of a program parameter-shaped tree,
    where `per_leaf` (`_norms`, `_samples`) gives each leaf's values, one
    row per stacked layer."""
    specs, n_pos = leaf_names(tree)
    out = {}
    for (path, kind), v in zip(specs,
                               jax.tree_util.tree_leaves(per_leaf(tree))):
        v = np.asarray(v, np.float64)
        out.update(zip(_names(path, kind, len(v), n_pos, n_groups), v))
    return out


def named_change(params, params0_host, n_groups: int) -> dict:
    """{leaf name: ||p - p0||} with p0 held on the host, moved back one
    leaf at a time."""
    specs, n_pos = leaf_names(params)
    out = {}
    for (path, kind), a, b in zip(specs, jax.tree_util.tree_leaves(params),
                                  jax.tree_util.tree_leaves(params0_host)):
        v = np.asarray(_diff_norms(a, jax.device_put(b),
                                   kind == "groups"), np.float64)
        out.update(zip(_names(path, kind, len(v), n_pos, n_groups), v))
    return out


# --------------------------------------------------------------------------
# the feed and the window
# --------------------------------------------------------------------------


class Feed:
    """Stands in for the loop's `host_batch`. The loop calls it at the
    top of each step, so the loop's `state` is the previous step's
    output:

    - step 0: the initial parameters, copied to the host;
    - step 1: the first gradient as AdamW got it (first moment / (1-b1)),
              its norm and a sample of its elements per leaf; and the
              memory of the compiled step;
    - step `check`: each leaf's change since step 0;
    - step `check + settle - 1`: one full collection of Python's
              garbage, and the set-up's objects frozen out of later ones;
    - step `check + settle`: the window opens at the previous mark;
    - later: once `seconds` have passed since it opened, the window
      closes at the previous mark and the loop is ended."""

    def __init__(self, orig, *, check: int, settle: int, seconds: float,
                 b1: float, n_groups: int, marks: StepMarks,
                 gc_pauses: GcPauses, on_open=None, on_close=None):
        self.orig = orig
        self.check, self.settle, self.seconds = check, settle, seconds
        self.b1, self.n_groups, self.marks = b1, n_groups, marks
        self.on_open, self.on_close = on_open, on_close
        self.gc_pauses = gc_pauses
        self.params0 = None
        self.first_grad = None
        self.first_sample = None
        self.change = None
        self.memory = None
        self.batches = []
        self.open_step = check + settle
        self.t_open = None
        self.close_step = None

    def __call__(self, pipe, step, host=0):
        batch = self.orig(pipe, step, host)
        loop = sys._getframe(1).f_locals
        missing = [n for n in LOOP_LOCALS if n not in loop]
        if missing:
            raise RuntimeError(
                f"the benchmark's feed reads the locals {LOOP_LOCALS} of "
                f"run_training in src/repro/train/loop.py, which calls "
                f"host_batch; its caller {sys._getframe(1).f_code.co_name}"
                f" has no {missing}")
        state = loop["state"]
        if step < self.check:
            self.batches.append(tuple(np.asarray(b) for b in batch))
        if step == 0:
            self.params0 = jax.device_get(state.params)
        if step == 1:
            m = named(_norms, state.opt["m"], self.n_groups)
            self.first_grad = {n: v / (1 - self.b1) for n, v in m.items()}
            m = named(_samples, state.opt["m"], self.n_groups)
            self.first_sample = {n: v / (1 - self.b1) for n, v in m.items()}
            tokens, labels = batch
            self.memory = loop["train_step"].lower(
                state, {"tokens": tokens, "labels": labels}
            ).compile().memory_analysis()
        if step == self.check:
            self.change = named_change(state.params, self.params0,
                                       self.n_groups)
            self.params0 = None
        if step == self.open_step - 1:
            self.gc_pauses.collect_and_freeze()
        if step == self.open_step:
            if self.on_open is not None:
                self.on_open()
            self.t_open = self.marks.marks[-1].t
        elif self.t_open is not None and \
                self.marks.marks[-1].t - self.t_open >= self.seconds:
            self.close_step = step - 1
            if self.on_close is not None:
                self.on_close()
            raise WindowClosed
        return batch


@dataclasses.dataclass
class Window:
    """What the program did: its readings for the comparison, the
    window's marks and the compiled step's memory."""
    loss: list
    grad_norm: list
    batches: list             # (tokens, labels) of the first steps
    first_grad: dict
    first_sample: dict
    change: dict
    marks: list               # the window's marks, first to last
    lowerings_setup: int
    compile_s: float
    memory: object            # compiled.memory_analysis()
    t_open: float
    gc: GcPauses


def run_window(arch, run, *, seed: int, seconds: float, check: int,
               settle: int, counter: CompileCounter, on_open=None,
               on_close=None) -> Window:
    """Runs the program's loop from the seed through its first steps
    and the window, and returns once its state is freed."""
    import repro.train.loop as loop_mod

    train_log = logging.getLogger("repro.train")
    train_log.setLevel(logging.INFO)
    marks = StepMarks(counter)
    train_log.addHandler(marks)
    pauses = GcPauses()
    feed = Feed(loop_mod.host_batch, check=check, settle=settle,
                seconds=seconds, b1=run.optimizer.b1,
                n_groups=arch.num_groups, marks=marks, gc_pauses=pauses,
                on_open=on_open, on_close=on_close)
    loop_mod.host_batch = feed
    gc.callbacks.append(pauses)
    try:
        with tempfile.TemporaryDirectory(prefix="bench_ckpt_") as d:
            loop = loop_mod.LoopConfig(num_steps=10 ** 9, ckpt_every=0,
                                       ckpt_dir=d, log_every=1)
            try:
                loop_mod.run_training(arch, run, loop, seed=seed)
            except WindowClosed:
                pass
            else:
                raise RuntimeError("the loop ended before the window "
                                   "closed")
    finally:
        loop_mod.host_batch = feed.orig
        train_log.removeHandler(marks)
        gc.callbacks.remove(pauses)
        gc.unfreeze()
    gc.collect()
    by_step = {m.step: m for m in marks.marks}
    win = [m for m in marks.marks
           if feed.open_step - 1 <= m.step <= feed.close_step]
    return Window(
        loss=[by_step[s].loss for s in range(check)],
        grad_norm=[by_step[s].grad_norm for s in range(check)],
        batches=feed.batches,
        first_grad=feed.first_grad, first_sample=feed.first_sample,
        change=feed.change, marks=win,
        lowerings_setup=win[0].lowerings, compile_s=counter.compile_s,
        memory=feed.memory, t_open=win[0].t, gc=pauses)


def step_hbm_bytes(memory) -> int:
    """Bytes the compiled step needs on the device: arguments, outputs
    and temporaries, less what outputs alias from donated arguments."""
    return (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes - memory.alias_size_in_bytes)


def window_numbers(w: Window, tokens_per_step: int) -> dict:
    """tokens/s over every step of the window and the 95th percentile of
    the intervals between its marks."""
    ts = np.array([m.t for m in w.marks])
    steps = len(ts) - 1
    span = float(ts[-1] - ts[0])
    iv = np.diff(ts)
    longest = np.argsort(iv)[::-1][:3]
    return {"steps": steps, "seconds": span,
            "longest": [(w.marks[i + 1].step, float(iv[i])) for i in longest],
            "tokens_per_s": steps * tokens_per_step / span,
            "step_s_p95": float(np.percentile(iv, 95)),
            "step_s_median": float(np.median(iv)),
            "window_compiles": w.marks[-1].lowerings - w.marks[0].lowerings}


