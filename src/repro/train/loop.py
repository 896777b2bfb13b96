"""Fault-tolerant training loop (DESIGN.md §4):

  * checkpoint/restart — atomic async saves every `ckpt_every`, resume
    from latest on start (data pipeline is stateless-resumable so the
    token stream continues exactly);
  * straggler watchdog — per-step wall-time EMA; steps slower than
    `straggler_factor` x EMA are counted and logged, and a budget of
    consecutive stragglers triggers checkpoint+abort so the scheduler can
    replace the node (exit code 75 = temp failure, retryable);
  * NaN guard — the step itself skips non-finite updates; `max_skips`
    consecutive skips triggers rewind to the last checkpoint;
  * adaptive rank — per-epoch controller call (paper Algorithm 1) with
    projection refresh via fold_in on rank change;
  * instrumentation — each step is one `StepTraceAnnotation` split into
    the host spans `telemetry.LOOP_SPANS` (feed, dispatch, sync,
    control, record, ckpt), each also a profiler `TraceAnnotation`;
    every history entry and telemetry record carries their seconds and
    `compiles`, the programs the step lowered (DESIGN.md §11).
"""
from __future__ import annotations

import dataclasses
import logging
import sys

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import (
    RESIDUAL_LAYOUT, Checkpointer, gather_per_worker, scatter_per_worker,
)
from repro.core.adaptive import adaptive_step
from repro.data.pipeline import PipelineConfig, host_batch
from repro.sketches import node_paths, refresh_tree
from repro.sketches.shard import refresh_sharded_tree
from repro.telemetry import (
    STEP_ANNOTATION, TelemetryLog, TelemetryRecord, compile_counter,
    monitor_report, span,
)
from repro.train.state import RunConfig, TrainState, init_train_state
from repro.train.step import (
    collective_plan, make_dp_train_step, make_train_step,
)

log = logging.getLogger("repro.train")

# Rank-change projection refresh, jitted ONCE per tree shape: fold_in
# re-derives the projections/psi and zeroes the sketches with every
# output shape equal to its input shape, so neither this function nor
# the train step ever recompiles on a rank change (DESIGN.md §1; the
# compilation-count test in tests/test_sketches.py asserts it).
refresh_sketch_tree = jax.jit(refresh_tree)
# same contract for the reduce-scatter layout's ShardedNodeTree
refresh_sharded_sketch_tree = jax.jit(refresh_sharded_tree)


def _refresh_sketch(sketch):
    """Shape-static projection refresh for either sketch layout."""
    if hasattr(sketch, "nodes"):
        return refresh_sketch_tree(sketch)
    return refresh_sharded_sketch_tree(sketch)


def _close_entry(entry: dict, spans: dict, compiles: int, *,
                 first: bool) -> None:
    """Completes a step's history entry with its host spans and the
    programs it lowered; a step after the first that lowered one is
    named in a warning."""
    entry["compiles"] = compiles
    entry["spans"] = spans
    if compiles and not first:
        log.warning("recompiled: %d program(s) lowered in step %d",
                    compiles, entry["step"])


@dataclasses.dataclass
class LoopConfig:
    num_steps: int = 100
    ckpt_every: int = 50              # 0: write no checkpoint (a
    #                                   fresh ckpt_dir then resumes none)
    ckpt_dir: str = "artifacts/ckpt"
    ckpt_keep: int = 3
    straggler_factor: float = 3.0
    straggler_budget: int = 10
    max_skips: int = 5
    log_every: int = 10
    steps_per_epoch: int = 0          # 0 disables the adaptive controller
    telemetry_path: str | None = None  # JSONL TelemetryRecord export
    #                                    (DESIGN.md §11); None disables


def run_training(cfg, run: RunConfig, loop: LoopConfig, *,
                 seed: int = 0, donate: bool = True, dp_mesh=None):
    """Single-host driver (the multi-pod path wraps this in launch/train
    with a mesh + sharded state). Returns (state, history).

    With `dp_mesh` set (and `run.dp_axis_name` naming one of its axes)
    the step is shard_map-ed data-parallel: state replicated, batch
    split over the axis, gradients crossing the wire dense (pmean) or
    as the count-sketch table + optional p2 value round."""
    pipe = PipelineConfig(seed=seed, global_batch=run.global_batch,
                          seq_len=run.seq_len, vocab=cfg.vocab_size)
    ckpt = Checkpointer(loop.ckpt_dir, keep=loop.ckpt_keep)
    key = jax.random.PRNGKey(seed)
    if dp_mesh is None:
        state = init_train_state(key, cfg, run)
    else:
        # built replicated in place: a state made on one device and then
        # broadcast would sit on that device twice during the first step
        from jax.sharding import NamedSharding, PartitionSpec as P
        state = jax.jit(
            lambda k: init_train_state(k, cfg, run),
            out_shardings=NamedSharding(dp_mesh, P()))(key)

    persistable = lambda s: s
    restore_state = ckpt.restore
    save_meta: dict = {}
    if dp_mesh is not None:
        train_step = jax.jit(make_dp_train_step(cfg, run, dp_mesh),
                             donate_argnums=(0,) if donate else ())
        ax = run.dp_axis_name
        members = ax if isinstance(ax, tuple) else (ax,)
        workers = 1
        for a in members:
            workers *= dp_mesh.shape[a]
        log.info("data-parallel shard_map step: %d-way %r axis",
                 workers, ax)
        cs_mode = run.compression is not None \
            and run.compression.mode == "countsketch"
        rs_mode = run.dp_merge == "reduce_scatter" \
            and state.sketch is not None
        # the int8 sketch wire's per-worker quantization ledger
        # (DESIGN.md §14) persists exactly like the countsketch
        # error feedback: stacked per worker, mass-split on elastic
        # restart
        i8_mode = "sketch_err" in state.opt
        if cs_mode or rs_mode or i8_mode:
            # the countsketch error-feedback accumulators (each
            # worker's unsent residual) and the rs sketch shards are
            # INTENTIONALLY per-worker: device-local buffers under the
            # replicated spec. A host-side checkpoint would silently
            # keep worker 0's copy and drop the rest, and the PR 2-era
            # pmean merge destroyed the decomposition at every save —
            # instead stack every worker's copy on a leading (W, ...)
            # axis and restore it exactly (DESIGN.md §12).
            save_meta = {"residual_layout": RESIDUAL_LAYOUT,
                         "dp_workers": workers}
            if rs_mode:
                save_meta["sketch_layout"] = "sharded-v1"

            def _split(s):
                pw = {}
                if cs_mode:
                    pw["err"] = s.opt["err"]
                if i8_mode:
                    pw["sketch_err"] = s.opt["sketch_err"]
                if rs_mode:
                    pw["flat"] = s.sketch.flat
                return pw

            def _join(s, pw):
                opt_keys = [k for k in ("err", "sketch_err") if k in pw]
                if opt_keys:
                    opt = dict(s.opt)
                    for k in opt_keys:
                        opt[k] = pw[k]
                    s = dataclasses.replace(s, opt=opt)
                if "flat" in pw:
                    s = dataclasses.replace(
                        s, sketch=dataclasses.replace(
                            s.sketch, flat=pw["flat"]))
                return s

            def persistable(s):
                return _join(
                    s, gather_per_worker(_split(s), dp_mesh, ax))

            def restore_state(s):
                from repro.sketches.shard import (
                    reshard_stacked_flat, shard_tree, template_tree,
                )

                meta0 = ckpt.metadata()
                layout = meta0.get("residual_layout")
                # merged-sketch (pre-§12 or psum-run) checkpoint under
                # an rs run: restore the replicated NodeTree and shard
                # it onto this worker count
                legacy_sketch = rs_mode and \
                    meta0.get("sketch_layout") != "sharded-v1"
                template = s
                if legacy_sketch:
                    template = dataclasses.replace(
                        s, sketch=template_tree(s.sketch))
                loaded, meta = ckpt.restore(template)
                pw = {}
                if legacy_sketch:
                    tiles = [shard_tree(loaded.sketch, workers, i)
                             for i in range(workers)]
                    ssk = dataclasses.replace(
                        tiles[0],
                        flat=jnp.stack([t.flat for t in tiles]))
                    loaded = dataclasses.replace(loaded, sketch=ssk)
                    pw["flat"] = ssk.flat
                    log.info("sharded merged-sketch checkpoint over "
                             "%d workers", workers)
                if layout == RESIDUAL_LAYOUT:
                    w_old = int(meta0.get("dp_workers", workers))
                    pw.update(_split(loaded))
                    if w_old != workers:
                        # elastic restart: sketch shards re-tile
                        # EXACTLY (positional relayout); err residuals
                        # mass-split total/W_new
                        if "flat" in pw and not legacy_sketch:
                            pw["flat"] = reshard_stacked_flat(
                                pw["flat"].reshape(w_old, -1),
                                state.sketch.spec, workers)
                        for rk in ("err", "sketch_err"):
                            if rk in pw:
                                pw[rk] = jax.tree.map(
                                    lambda x: jnp.broadcast_to(
                                        x.sum(0) / workers,
                                        (workers,) + x.shape[1:]),
                                    pw[rk])
                        log.info("elastic residual reshard %d -> %d "
                                 "workers", w_old, workers)
                elif layout is not None:
                    raise ValueError(
                        f"unknown residual_layout {layout!r}")
                if pw:
                    loaded = _join(
                        loaded, scatter_per_worker(pw, dp_mesh, ax))
                return loaded, meta
    else:
        train_step = jax.jit(make_train_step(cfg, run),
                             donate_argnums=(0,) if donate else ())

    start = ckpt.latest_step()
    if start is not None:
        state, meta = restore_state(state)
        log.info("restored checkpoint at step %s", meta["step"])
    step0 = int(state.step)
    history = []
    ema_t = None
    stragglers = 0
    consec_skips = 0
    last_skip_total = int(state.skipped)

    # telemetry (DESIGN.md §11): the compiled step already writes sketch
    # metrics into the in-device ring buffer; the host drains it into
    # the shared train+serve schema. Structural wire accounting comes
    # from the collective layout, not runtime introspection.
    tlog = TelemetryLog(loop.telemetry_path) \
        if loop.telemetry_path else None
    plan = collective_plan(
        cfg, run,
        mesh_shape=dict(dp_mesh.shape) if dp_mesh is not None else None
    ) if tlog is not None else None
    sk_paths = node_paths(state.sketch) \
        if state.sketch is not None else []

    # each step runs as the host spans LOOP_SPANS (DESIGN.md §11), on
    # the profiler's timeline when it traces; a step's telemetry record
    # is written in the next step's loop/record, once its spans are in
    counter = compile_counter()
    done = None
    for step in range(step0, loop.num_steps):
        spans: dict = {}
        lowered = counter.lowerings
        with jax.profiler.StepTraceAnnotation(STEP_ANNOTATION,
                                              step_num=step):
            with span(spans, "loop/feed"):
                tokens, labels = host_batch(pipe, step)
            with span(spans, "loop/dispatch"):
                state, metrics = train_step(state, {"tokens": tokens,
                                                    "labels": labels})
            with span(spans, "loop/sync"):
                metrics = {k: float(v) for k, v in metrics.items()}
            dt = spans["loop/dispatch"] + spans["loop/sync"]

            with span(spans, "loop/control"):
                # straggler watchdog
                if ema_t is None:
                    ema_t = dt
                if dt > loop.straggler_factor * ema_t:
                    stragglers += 1
                    log.warning("straggler step %d: %.3fs vs EMA %.3fs",
                                step, dt, ema_t)
                    if stragglers >= loop.straggler_budget:
                        log.error("straggler budget exhausted; "
                                  "checkpoint+abort")
                        ckpt.save(step + 1, persistable(state),
                                  metadata=save_meta)
                        sys.exit(75)
                else:
                    stragglers = 0
                ema_t = 0.9 * ema_t + 0.1 * dt

                # NaN-guard rewind
                new_skip_total = int(metrics["skipped_total"])
                consec_skips = consec_skips + 1 \
                    if new_skip_total > last_skip_total else 0
                last_skip_total = new_skip_total
                if consec_skips >= loop.max_skips and \
                        ckpt.latest_step() is not None:
                    log.error("%d consecutive skipped steps; rewinding",
                              consec_skips)
                    state, _ = restore_state(state)
                    consec_skips = 0
                    continue

                # adaptive rank controller (per pseudo-epoch)
                if (loop.steps_per_epoch and run.adaptive is not None
                        and state.sketch is not None
                        and (step + 1) % loop.steps_per_epoch == 0):
                    adaptive, new_rank, changed = adaptive_step(
                        state.adaptive, state.sketch.rank,
                        jnp.asarray(metrics["loss"], jnp.float32),
                        run.adaptive)
                    sketch = dataclasses.replace(state.sketch,
                                                 rank=new_rank)
                    if bool(changed):
                        # paper Alg. 1 "reinitialize matrices": zero
                        # sketches + fold_in fresh projections,
                        # shape-static (no recompile)
                        sketch = _refresh_sketch(sketch)
                        log.info("rank change -> %d at step %d "
                                 "(projection refresh, epoch %d)",
                                 int(new_rank), step, int(sketch.epoch))
                    state = dataclasses.replace(state, adaptive=adaptive,
                                                sketch=sketch)

            with span(spans, "loop/record"):
                if done is not None:
                    tlog.append(done)
                spans["step"] = dt
                entry = {"step": step, "time_s": dt, **metrics}
                history.append(entry)
                nodes, flags = {}, {}
                if tlog is not None and state.sketch is not None \
                        and step % loop.log_every == 0:
                    # ring drain (one small device->host copy) only on
                    # log steps — the per-step record stays scalars +
                    # spans
                    nodes, flags = monitor_report(
                        state.monitor, sk_paths,
                        int(2 * state.sketch.rank + 1))
                if step % loop.log_every == 0:
                    log.info("step %d loss %.4f grad_norm %.3f (%.3fs)",
                             step, metrics["loss"], metrics["grad_norm"],
                             dt)
            with span(spans, "loop/ckpt"):
                if loop.ckpt_every and (step + 1) % loop.ckpt_every == 0:
                    ckpt.save_async(step + 1, persistable(state),
                                    metadata=save_meta)

        _close_entry(entry, spans, counter.lowerings - lowered,
                     first=step == step0)
        if tlog is not None:
            done = TelemetryRecord(
                kind="train", step=step,
                scalars={**metrics, "compiles": entry["compiles"]},
                nodes=nodes, flags=flags, spans=spans,
                wire_bytes=plan["wire_bytes"],
                collectives=plan["collectives"],
                mesh=plan["mesh"],
                per_axis_collectives=plan["per_axis"])

    ckpt.wait()
    if loop.ckpt_every:
        ckpt.save(loop.num_steps, persistable(state), metadata=save_meta)
    if tlog is not None:
        if done is not None:
            tlog.append(done)
        tlog.close()
    return state, history


def run_training_sharded(cfg, run: RunConfig, loop: LoopConfig, mesh,
                         rules, *, seed: int = 0):
    """Mesh-aware wrapper: installs the sharding rules, places the train
    state per the logical-axis rules (elastic restore reshards onto THIS
    mesh regardless of the checkpoint's source mesh), and runs the same
    fault-tolerant loop."""
    import jax

    from repro.parallel.sharding import param_shardings, use_rules

    with use_rules(rules), mesh:
        pipe = PipelineConfig(seed=seed, global_batch=run.global_batch,
                              seq_len=run.seq_len, vocab=cfg.vocab_size)
        ckpt = Checkpointer(loop.ckpt_dir, keep=loop.ckpt_keep)
        state = init_train_state(jax.random.PRNGKey(seed), cfg, run)
        shardings = param_shardings(rules, state)
        if ckpt.latest_step() is not None:
            state, meta = ckpt.restore(state, shardings=shardings)
            log.info("elastic restore at step %s onto mesh %s",
                     meta["step"], dict(mesh.shape))
        else:
            state = jax.device_put(state, shardings)
        step_fn = jax.jit(make_train_step(cfg, run))
        history = []
        step0 = int(state.step)
        counter = compile_counter()
        for step in range(step0, loop.num_steps):
            spans: dict = {}
            lowered = counter.lowerings
            with jax.profiler.StepTraceAnnotation(STEP_ANNOTATION,
                                                  step_num=step):
                with span(spans, "loop/feed"):
                    tokens, labels = host_batch(pipe, step)
                with span(spans, "loop/dispatch"):
                    state, metrics = step_fn(state, {"tokens": tokens,
                                                     "labels": labels})
                with span(spans, "loop/sync"):
                    metrics = {k: float(v) for k, v in metrics.items()}
                with span(spans, "loop/record"):
                    spans["step"] = spans["loop/dispatch"] + \
                        spans["loop/sync"]
                    entry = {"step": step, "time_s": spans["step"],
                             **metrics}
                    history.append(entry)
                    if step % loop.log_every == 0:
                        log.info("step %d loss %.4f", step,
                                 metrics["loss"])
                with span(spans, "loop/ckpt"):
                    if loop.ckpt_every and \
                            (step + 1) % loop.ckpt_every == 0:
                        ckpt.save_async(step + 1, state)
            _close_entry(entry, spans, counter.lowerings - lowered,
                         first=step == step0)
        ckpt.wait()
        if loop.ckpt_every:
            ckpt.save(loop.num_steps, state)
    return state, history
