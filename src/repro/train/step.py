"""The jitted training step: forward + sketched/standard backward +
AdamW + NaN guard + sketch monitoring, all inside one XLA program."""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.monitor import monitor_record, tree_metrics
from repro.models.transformer import forward, sketch_groups
from repro.optim.adamw import adamw_update
from repro.optim.compression import (
    compress_grads, compressed_bytes, init_error_feedback,
)
from repro.optim.sketched_sgd import compress_grads_countsketch
from repro.optim.schedule import warmup_cosine
from repro.parallel.sharding import constrain
from repro.telemetry import scope
from repro.train.state import RunConfig, TrainState, finalize_run


def cross_entropy(logits, labels, z_weight: float = 0.0):
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    true = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    ce = (lse - true).mean()
    if z_weight > 0:
        ce = ce + z_weight * (lse ** 2).mean()
    return ce


class _WireOut(NamedTuple):
    """Post-merge products of the flat-segment wire exchange."""
    loss: Any
    ce: Any
    aux: Any
    grads: Any        # gradient pytree — None while a p2 round is
    #                   pending (`p2` then holds the deferred exchange)
    err: Any          # new gradient-compression error feedback, or None
    sketch: Any       # merged sketch increments (fused layout), or None
    sketch_err: Any   # new int8 sketch-wire residual ledger, or None
    p2: Any           # (local, merged_cs, workers) when the p2 round
    #                   is deferred to overlap the optimizer, else None


# Segments that must stay EXACT f32 on the wire when the int8 ring
# carries the sketch increments: worker counters and loss scalars (a
# shared per-chunk scale would corrupt them outright), the count-sketch
# table (its int8 wire has its OWN per-row grid + error feedback — see
# optim/sketched_sgd.py), and dense grads (no residual ledger of their
# own). They ride one small f32 psum alongside the ring.
_RING_EXEMPT = ("n", "scalars", "cs_table", "grads")


def _psum_wire_segments(run, ax, err_state, grads, loss, ce, aux, *,
                        sketch_leaves=None, sketch_err=None,
                        p2_defer=False, name):
    """THE flat-segment gradient-wire exchange shared by the fused and
    overlap layouts (DESIGN.md §9/§10): pack the gradient wire (the
    count-sketch table — int8-grid values under wire_dtype="int8" — or
    the dense grads), the scalar metrics and a constant-1 worker
    counter — plus, for the fused single-collective layout, every
    sketch node's local increments (``sketch_leaves``) — into ONE flat
    psum, and post-process the merge. Under ``run.ring_wire`` the
    buffer crosses the Pallas remote-DMA ring instead (DESIGN.md §14);
    under ``run.sketch_wire_dtype="int8"`` the sketch increments are
    quantized for the wire with the rounding residual folded into the
    per-worker ``sketch_err`` ledger (mass catch-up: the wire carries
    inc + last step's residual).

    With ``p2_defer`` (countsketch, cs_p2 > 0) the p2 exact-value round
    is NOT issued here: the un-finished exchange comes back in ``p2``
    so the caller can overlap it with the optimizer update.

    Segment offsets are static (memoized at NodeTree init); the
    collective count is asserted by the differential tier and the bench
    gate."""
    from repro.parallel.collectives import psum_flat_segments
    from repro.sketches.wire import partition_segments

    cs_mode = run.compression is not None and \
        run.compression.mode == "countsketch"
    segments = {
        "n": jnp.ones((), jnp.float32),
        "scalars": jnp.stack([loss, ce, aux]),
    }
    new_sketch_err = None
    if sketch_leaves is not None:
        if run.sketch_wire_dtype == "int8":
            # mass catch-up (DESIGN.md §14): this step's wire carries
            # inc + the residual last step's quantization left behind,
            # so the merged EMA trajectory telescopes to f32 up to one
            # outstanding residual per worker
            from repro.sketches.wire import fake_quantize_tree
            inc_adj = jax.tree.map(jnp.add, sketch_leaves, sketch_err)
            if run.ring_wire:
                # the int8 ring quantizes per hop itself — ship the
                # adjusted increments raw; its ledger comes back from
                # the collective below
                segments["sketch"] = inc_adj
            else:
                dhat, new_sketch_err = fake_quantize_tree(inc_adj)
                segments["sketch"] = dhat
        else:
            segments["sketch"] = sketch_leaves
    local = None
    if cs_mode:
        from repro.optim.sketched_sgd import countsketch_local
        local = countsketch_local(grads, err_state, run.compression)
        segments["cs_table"] = local.cs.table
    else:
        # dense DP wire (also carries topk mode — top-k is NOT
        # psum-mergeable, so under DP it rides the dense sum and its
        # sparsification happens post-merge)
        segments["grads"] = grads
    if sketch_leaves is None:
        # overlap's LATE psum (or sketching off): nothing early-keyed
        # may ride this buffer — partition_segments is the single
        # definition of the early/late split, so a segment added to
        # OVERLAP_EARLY_KEYS without a matching early psum fails loudly
        # at trace time instead of silently re-serializing the schedule
        early, segments = partition_segments(segments)
        if early:
            raise ValueError(
                f"early-keyed segments {sorted(early)} on the late "
                f"wire psum — they must ride the early collective")
    if run.ring_wire and run.sketch_wire_dtype == "int8" \
            and "sketch" in segments:
        merged, ring_res = psum_flat_segments(
            segments, ax, name=name, ring="int8",
            ring_workers=run.dp_workers, ring_exempt=_RING_EXEMPT)
        new_sketch_err = ring_res["sketch"]
    elif run.ring_wire:
        merged = psum_flat_segments(
            segments, ax, name=name, ring="fp32",
            ring_workers=run.dp_workers)
    else:
        merged = psum_flat_segments(segments, ax, name=name)
    workers = merged["n"]
    loss = merged["scalars"][0] / workers
    ce = merged["scalars"][1] / workers
    aux = merged["scalars"][2] / workers
    new_err = None
    p2 = None
    if cs_mode:
        import dataclasses as _dc

        from repro.optim.sketched_sgd import countsketch_finish
        merged_cs = _dc.replace(local.cs, table=merged["cs_table"])
        if p2_defer and run.compression.cs_p2 > 0:
            grads = None
            p2 = (local, merged_cs, workers)
        else:
            grads, new_err, _ = countsketch_finish(
                local, merged_cs, workers=workers, axis_name=ax)
    else:
        grads = jax.tree.map(lambda g: g / workers, merged["grads"])
        if run.compression is not None:
            grads, new_err, _ = compress_grads(
                grads, err_state, run.compression)
    return _WireOut(loss, ce, aux, grads, new_err,
                    merged.get("sketch"), new_sketch_err, p2)


def _apply_merged_increments(old_tree, inc_tree, merged_leaves, beta):
    """Fold the psum-merged per-node increments into the previous
    step's tree: ``mask(beta * old + inc)`` per x/y/z leaf — the exact
    accumulate formula of the per-node-psum path, so the resulting tree
    is bitwise identical to it (DESIGN.md §9)."""
    import dataclasses

    from repro.sketches.update import ema_apply_increment

    k_active = inc_tree.k_active
    nodes = {}
    for name, node in old_tree.nodes.items():
        m = merged_leaves[name]
        nodes[name] = dataclasses.replace(
            inc_tree.nodes[name],
            x=ema_apply_increment(node.x, m["x"], beta, k_active),
            y=ema_apply_increment(node.y, m["y"], beta, k_active),
            z=ema_apply_increment(node.z, m["z"], beta, k_active),
        )
    return dataclasses.replace(inc_tree, nodes=nodes)


def make_train_step(cfg: ArchConfig, run: RunConfig):
    import dataclasses

    run = finalize_run(cfg, run)
    ax = run.dp_axis_name
    groups = sketch_groups(cfg) if run.sketch.enabled else {}
    consumed = bool(groups) and "res" not in groups
    # The overlap schedule (DESIGN.md §10) only pays its second
    # collective when the backward actually CONSUMES the merged triple
    # (sketched-backprop trees). Monitor-mode trees — or sketching off —
    # have no consumer, so overlap degrades to the fused
    # single-collective fast path, which is already bitwise-exact for
    # them.
    overlap = ax is not None and run.dp_collective == "overlap" \
        and consumed
    fused = ax is not None and not overlap and \
        run.dp_collective in ("fused", "overlap")
    # ZeRO-style sketch merge (DESIGN.md §12): TrainState.sketch is a
    # ShardedNodeTree; the increment psum becomes a reduce-scatter and
    # one all-gather reconstitutes the merged triple for its genuine
    # consumers (phase-2 backward / monitor metrics).
    rs = run.dp_merge == "reduce_scatter" and bool(groups)
    # re-run the RunConfig compatibility matrix with the one
    # architecture-dependent fact it lacks at construction: whether the
    # backward CONSUMES the merged triple (state.ConfigError, §15)
    run.validate(consumed=consumed)
    if fused and run.sketch.enabled and not run.sketch.dp_defer:
        # fused mode moves the sketch merge out of the forward: the
        # forward must emit LOCAL increments (dp_defer), never per-node
        # psums (dp_axis)
        run = dataclasses.replace(
            run, sketch=dataclasses.replace(
                run.sketch, dp_defer=True, dp_axis=None))
    # overlap phase settings: phase 1 emits local increments (dp_defer),
    # phase 2 consumes the merged tree as-is (dp_premerged)
    defer_st = dataclasses.replace(
        run.sketch, dp_defer=True, dp_axis=None)
    premerged_st = dataclasses.replace(
        run.sketch, dp_defer=False, dp_axis=None, dp_premerged=True)
    # p2-overlap (DESIGN.md §14): on the flat-wire layouts the p2
    # exact-value round is deferred past the wire merge and hidden
    # behind the zero-grad dense optimizer pass — bitwise the serial
    # nominate -> psum -> complete -> adamw composition. per_node and
    # the rs layout keep the serial reference.
    p2o = run.p2_overlap and run.compression is not None and \
        run.compression.mode == "countsketch" and \
        run.compression.cs_p2 > 0 and (fused or overlap)

    def train_step(state: TrainState, batch):
        tokens = constrain(batch["tokens"], "batch", "none")
        labels = constrain(batch["labels"], "batch", "none")

        def loss_fn(params, sketch):
            out = forward(
                params, tokens, cfg=cfg, mode="train",
                sketch_state=sketch, settings=run.sketch,
                patch_embeds=batch.get("patch_embeds"))
            ce = cross_entropy(out["logits"], labels, run.z_weight)
            loss = ce + run.aux_weight * out["aux"]
            return loss, (out["sketch_state"], ce, out["aux"])

        new_err = None
        new_sketch_err = None
        p2 = None
        merged_tree = None
        if rs:
            # ---- REDUCE-SCATTER MERGE (DESIGN.md §12) ---------------
            # Exactly 3 dp collectives regardless of fused/overlap:
            #   RS  the packed local increments -> this worker's tile
            #       of the merged buffer (bitwise the psum's tile);
            #   AG  the new shards -> the full CURRENT-step merged
            #       triple for its consumers (phase-2 backward under
            #       overlap, monitor metrics always);
            #   AR  the late gradient wire + scalar metrics.
            # The EMA apply runs on the 1/W flat shard — per-worker
            # sketch memory is the ZeRO win the memory bench gates.
            from repro.parallel.collectives import (
                all_gather_flat, reduce_scatter_flat_segments,
            )
            from repro.sketches.shard import (
                apply_shard_increments, template_tree, unshard_tree,
            )
            from repro.sketches.wire import tree_increment_leaves

            ssk = state.sketch
            widx = jax.lax.axis_index(ax)
            if overlap:
                # phase 1: increment-emission sweep (template has zero
                # triples + the real psi/proj — all the emission reads)
                inc_out = forward(
                    state.params, tokens, cfg=cfg, mode="train",
                    sketch_state=template_tree(ssk), settings=defer_st,
                    patch_embeds=batch.get("patch_embeds"))
                inc_tree = inc_out["sketch_state"]
            else:
                (loss, (inc_tree, ce, aux)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params,
                                           template_tree(ssk))
            inc_shard = reduce_scatter_flat_segments(
                tree_increment_leaves(inc_tree), ax, shards=ssk.shards,
                spec=ssk.spec, name="rs_sketch", barrier=overlap)
            new_sketch = apply_shard_increments(
                ssk, inc_tree, inc_shard, run.sketch.beta, widx)
            merged_tree = unshard_tree(
                new_sketch,
                all_gather_flat(new_sketch.flat, ax, name="rs_gather",
                                barrier=overlap))
            if overlap:
                # phase 2: backward consumes THIS step's merged triple
                # (premerged, current-step DP-exact — same as overlap)
                def rs_loss_fn(params, sketch):
                    out = forward(
                        params, tokens, cfg=cfg, mode="train",
                        sketch_state=sketch, settings=premerged_st,
                        patch_embeds=batch.get("patch_embeds"))
                    ce = cross_entropy(out["logits"], labels,
                                       run.z_weight)
                    loss = ce + run.aux_weight * out["aux"]
                    return loss, (ce, out["aux"])

                (loss, (ce, aux)), grads = jax.value_and_grad(
                    rs_loss_fn, has_aux=True)(state.params, merged_tree)
            w = _psum_wire_segments(
                run, ax, state.opt.get("err"), grads, loss, ce, aux,
                name="rs_grad")
            loss, ce, aux, grads, new_err = \
                w.loss, w.ce, w.aux, w.grads, w.err
        elif overlap:
            # ---- TWO-PHASE OVERLAP SCHEDULE (DESIGN.md §10) ---------
            # Phase 1: a forward sweep emits every node's LOCAL EMA
            # increments, and the sketch flat psum is issued IMMEDIATELY
            # — before the differentiated forward/backward below — so
            # XLA can hide its latency behind the backward sweep. The
            # merged triple is folded in (same accumulate as per_node,
            # bitwise) and phase 2's backward consumes THIS step's
            # merged triple through sketched_matmul's residuals: the
            # fused layout's one-step consumption lag is gone. Only the
            # logits head of this sweep is dead code (DCE'd); the
            # activation matmuls it shares with phase 2 are CSE-able.
            from repro.parallel.collectives import psum_flat_segments
            from repro.sketches.wire import tree_increment_leaves

            inc_out = forward(
                state.params, tokens, cfg=cfg, mode="train",
                sketch_state=state.sketch, settings=defer_st,
                patch_embeds=batch.get("patch_embeds"))
            inc_tree = inc_out["sketch_state"]
            inc_leaves = tree_increment_leaves(inc_tree)
            if run.sketch_wire_dtype == "int8":
                # the early buffer is PURE sketch increments — mass
                # catch-up applies to the whole tree (wire carries
                # inc + last step's quantization residual)
                inc_adj = jax.tree.map(jnp.add, inc_leaves,
                                       state.opt["sketch_err"])
                if run.ring_wire:
                    # whole-buffer int8 ring: the ring quantizes per
                    # hop; its residual ledger IS the new sketch_err
                    merged_inc, new_sketch_err = psum_flat_segments(
                        inc_adj, ax, name="overlap_sketch",
                        barrier=True, ring="int8",
                        ring_workers=run.dp_workers)
                else:
                    from repro.sketches.wire import fake_quantize_tree
                    dhat, new_sketch_err = fake_quantize_tree(inc_adj)
                    merged_inc = psum_flat_segments(
                        dhat, ax, name="overlap_sketch", barrier=True)
            elif run.ring_wire:
                merged_inc = psum_flat_segments(
                    inc_leaves, ax, name="overlap_sketch",
                    barrier=True, ring="fp32",
                    ring_workers=run.dp_workers)
            else:
                merged_inc = psum_flat_segments(
                    inc_leaves, ax, name="overlap_sketch", barrier=True)
            new_sketch = _apply_merged_increments(
                state.sketch, inc_tree, merged_inc, run.sketch.beta)

            # Phase 2: loss + backward. The primal never reads the
            # triple (sketched_matmul's forward is a plain matmul), so
            # only the backward's reconstructions wait on the early
            # collective.
            def overlap_loss_fn(params, sketch):
                out = forward(
                    params, tokens, cfg=cfg, mode="train",
                    sketch_state=sketch, settings=premerged_st,
                    patch_embeds=batch.get("patch_embeds"))
                ce = cross_entropy(out["logits"], labels, run.z_weight)
                loss = ce + run.aux_weight * out["aux"]
                return loss, (ce, out["aux"])

            (loss, (ce, aux)), grads = jax.value_and_grad(
                overlap_loss_fn, has_aux=True)(state.params, new_sketch)

            # Late collective: gradient wire + metrics + worker counter
            # — the same segments the fused layout packs, minus the
            # sketch increments that already rode the early psum.
            w = _psum_wire_segments(
                run, ax, state.opt.get("err"), grads, loss, ce, aux,
                p2_defer=p2o, name="overlap_grad")
            loss, ce, aux, grads, new_err, p2 = \
                w.loss, w.ce, w.aux, w.grads, w.err, w.p2
        elif fused:
            (loss, (new_sketch, ce, aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, state.sketch)
            # ---- ONE collective per step (DESIGN.md §9) -------------
            # Everything that crosses the DP axis rides a single flat
            # f32 psum: the sketch increments + the gradient wire + the
            # metrics + the worker counter.
            from repro.sketches.wire import tree_increment_leaves

            sketch_leaves = tree_increment_leaves(new_sketch) \
                if new_sketch is not None else None
            w = _psum_wire_segments(
                run, ax, state.opt.get("err"), grads, loss, ce,
                aux, sketch_leaves=sketch_leaves,
                sketch_err=state.opt.get("sketch_err"),
                p2_defer=p2o, name="fused_step")
            loss, ce, aux, grads, new_err, merged_sketch = \
                w.loss, w.ce, w.aux, w.grads, w.err, w.sketch
            new_sketch_err, p2 = w.sketch_err, w.p2
            if new_sketch is not None:
                new_sketch = _apply_merged_increments(
                    state.sketch, new_sketch, merged_sketch,
                    run.sketch.beta)
        else:
            (loss, (new_sketch, ce, aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, state.sketch)
            if ax is not None:
                # per-shard losses -> global means, so every replica
                # takes the same NaN-guard branch and logs the same
                # numbers
                loss = jax.lax.pmean(loss, ax)
                ce = jax.lax.pmean(ce, ax)
                aux = jax.lax.pmean(aux, ax)
                if new_sketch is not None and run.sketch.dp_axis is None:
                    # legacy approximation: average the float leaves so
                    # replicas stay in sync. With run.sketch.dp_axis set
                    # (make_dp_train_step per_node), the forward already
                    # psum-ed the per-token increments — DP-EXACT
                    # full-batch semantics (DESIGN.md §4) — and every
                    # replica holds identical sketches; no post-hoc
                    # collective is needed.
                    new_sketch = jax.tree.map(
                        lambda x: jax.lax.pmean(x, ax)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x,
                        new_sketch)

            if run.compression is not None and \
                    run.compression.mode == "countsketch":
                # Mergeable path: workers exchange an O(r*c) linear
                # sketch (exact under psum) instead of the dense grad;
                # the update is identical on every worker afterwards.
                grads, new_err, _ = compress_grads_countsketch(
                    grads, state.opt["err"], run.compression,
                    axis_name=ax)
            else:
                if ax is not None:
                    # dense DP wire: the baseline all-reduce countsketch
                    # replaces — O(D) bytes across the axis. NOTE: top-k
                    # sparsification is NOT psum-mergeable, so under DP
                    # it rides this dense collective and saves no wire
                    # bytes; its compressed_bytes() accounting applies
                    # only to a (index, value)-shipping aggregation it
                    # doesn't have here. Use mode="countsketch" for real
                    # DP wire savings.
                    grads = jax.tree.map(
                        lambda g: jax.lax.pmean(g, ax), grads)
                if run.compression is not None:
                    grads, new_err, _ = compress_grads(
                        grads, state.opt["err"], run.compression)

        lr_scale = warmup_cosine(
            state.step, warmup_steps=run.warmup_steps,
            total_steps=run.total_steps)
        opt_in = {k: v for k, v in state.opt.items()
                  if k not in ("err", "sketch_err")}
        if p2 is not None:
            # ---- OVERLAPPED p2 ROUND (DESIGN.md §14) ----------------
            # Issue the p2 exact-value all-reduce, run the dense AdamW
            # pass on ZERO grads while it is in flight (no data
            # dependency on the collective), then correct exactly the
            # k winning coordinates from the pre-update state —
            # bitwise the serial finish + adamw_update composition
            # (the differential tier asserts it). The barrier fences
            # the p2 payload AND the optimizer inputs at one issue
            # point, so XLA can neither sink the collective past the
            # update nor fold it into the wire merge.
            from repro.optim.adamw import adamw_sparse_update
            from repro.optim.sketched_sgd import (
                countsketch_complete, countsketch_nominate,
            )
            from repro.parallel.collectives import traced_psum
            local, merged_cs, wk = p2
            cand, exact = countsketch_nominate(local, merged_cs)
            exact, params_in, opt_in = jax.lax.optimization_barrier(
                (exact, state.params, opt_in))
            exact = traced_psum(exact, ax, name="cs_p2_values")
            update, sel_idx, _, new_err, _ = countsketch_complete(
                local, merged_cs, cand, exact, workers=wk)
            new_params, new_opt, om = adamw_sparse_update(
                params_in, opt_in, run.optimizer, lr_scale,
                update=update, idx=sel_idx, unravel=local.unravel)
        else:
            new_params, new_opt, om = adamw_update(
                state.params, grads, opt_in, run.optimizer, lr_scale)
        if new_err is not None:
            new_opt["err"] = new_err
        if new_sketch_err is not None:
            new_opt["sketch_err"] = new_sketch_err

        good = jnp.isfinite(loss) & jnp.isfinite(om["grad_norm"])
        pick = lambda n, o: jax.tree.map(
            lambda a, b: jnp.where(good, a, b), n, o)
        if run.nan_guard:
            # XLA fuses the optimizer's update into this select and
            # names the fusion after it: the select shares its scope
            with scope("adamw"):
                new_params = pick(new_params, state.params)
                new_opt = pick(new_opt, state.opt)
            if new_sketch is not None:
                new_sketch = pick(new_sketch, state.sketch)

        monitor = state.monitor
        if merged_tree is not None:
            # rs: metrics come from the gathered CURRENT-step merge —
            # bitwise the replicated layouts' recorded tree on kept
            # steps. On a NaN-skipped step the merge reflects the
            # discarded update, so the ring skips the record instead of
            # re-recording the kept tree (keeps NaN metrics out).
            rec = monitor_record(monitor, tree_metrics(merged_tree))
            monitor = pick(rec, monitor) if run.nan_guard else rec
        elif new_sketch is not None:
            monitor = monitor_record(monitor, tree_metrics(new_sketch))

        new_state = TrainState(
            params=new_params, opt=new_opt, sketch=new_sketch,
            adaptive=state.adaptive, monitor=monitor,
            step=state.step + 1,
            skipped=state.skipped + (~good).astype(jnp.int32),
        )
        metrics = {"loss": loss, "ce": ce, "aux": aux,
                   "grad_norm": om["grad_norm"],
                   "lr_scale": lr_scale,
                   "skipped_total": new_state.skipped}
        return new_state, metrics

    return train_step


def collective_plan(cfg: ArchConfig, run: RunConfig,
                    num_params: int | None = None,
                    mesh_shape: dict | None = None) -> dict:
    """Structural per-step DP accounting for telemetry (DESIGN.md §11):
    how many collectives one train step issues across the DP axis under
    this run's collective layout, and how many bytes one worker puts on
    the wire. Pure bookkeeping from the configs — mirrors the layout
    selection in `make_train_step` (the HLO collective counts themselves
    are asserted by tests/test_distributed.py); never traced.

    Every plan carries the mesh-aware fields (DESIGN.md §12):
    ``mesh`` (axis -> size, from `mesh_shape`), ``by_kind`` (all_reduce
    / reduce_scatter / all_gather tallied separately — the rs layouts
    split the old single all-reduce count), and ``per_axis`` (collective
    count per mesh axis; the dp superaxis is labeled "a+b". Non-dp axes
    carry 0 — TP traffic is GSPMD-implicit, not step-issued).
    """
    run = finalize_run(cfg, run)
    ax = run.dp_axis_name
    label = "+".join(ax) if isinstance(ax, tuple) else ax
    mesh = dict(mesh_shape) if mesh_shape else {}

    def _plan(layout, wire_bytes, *, ar=0, rs=0, ag=0,
              p2_overlap=False):
        per_axis = {} if ax is None else {label: ar + rs + ag}
        dp_members = set(ax if isinstance(ax, tuple) else (ax,)) \
            if ax is not None else set()
        for a in mesh:
            if a not in dp_members and a != label:
                per_axis[a] = 0
        return {"layout": layout, "collectives": ar + rs + ag,
                "wire_bytes": wire_bytes, "mesh": mesh,
                "by_kind": {"all_reduce": ar, "reduce_scatter": rs,
                            "all_gather": ag},
                "per_axis": per_axis,
                # DESIGN.md §14: collective COUNTS are unchanged by the
                # quantized/overlapped wire — these flags record which
                # of them ride the ring / hide behind the optimizer
                "ring_wire": run.ring_wire,
                "sketch_wire_dtype": run.sketch_wire_dtype,
                "p2_overlap": p2_overlap}

    if ax is None:
        return _plan("single_program", 0)
    groups = sketch_groups(cfg) if run.sketch.enabled else {}
    consumed = bool(groups) and "res" not in groups
    overlap = run.dp_collective == "overlap" and consumed
    fused = not overlap and run.dp_collective in ("fused", "overlap")
    rs = run.dp_merge == "reduce_scatter" and bool(groups)
    cs = run.compression is not None and \
        run.compression.mode == "countsketch"
    cs_p2 = 1 if cs and run.compression.cs_p2 > 0 else 0
    p2o = run.p2_overlap and cs_p2 > 0 and not rs and \
        run.dp_collective in ("fused", "overlap")

    if num_params is None:
        from repro.models.transformer import abstract_params
        params = abstract_params(cfg)
        num_params = sum(l.size for l in jax.tree.leaves(params))
        num_leaves = len(jax.tree.leaves(params))
    else:
        num_leaves = 1

    # sketch increments that cross the wire: 3 (stack..., w, k_max) f32
    # leaves per node — identical payload in all three sketching
    # layouts. Entry counts come from the registry specs (the real node
    # shapes), so position-restricted carry nodes and per-expert
    # (L, E, ...) stacks are accounted exactly — not the old
    # n_groups * num_layers approximation. The int8 wire ships 1 byte
    # per element + one f32 scale per stacked row
    # (sketches/wire.int8_segment_bytes is the per-spec source of truth)
    from repro.sketches.registry import node_specs_for

    def _stack_entries(spec) -> int:
        if spec.layers is None:
            return 1
        if isinstance(spec.layers, tuple):
            n = 1
            for s in spec.layers:
                n *= s
            return n
        return spec.layers

    specs = node_specs_for(cfg) if run.sketch.enabled else {}
    n_entries = sum(_stack_entries(s) for s in specs.values())
    if run.sketch_wire_dtype == "int8":
        sketch_bytes = sum(
            3 * _stack_entries(s) * s.width * (run.sketch.k_max * 1 + 4)
            for s in specs.values())
    else:
        sketch_bytes = sum(
            3 * _stack_entries(s) * s.width * run.sketch.k_max * 4
            for s in specs.values())
    grad_bytes = compressed_bytes(num_params, run.compression) if cs \
        else num_params * 4

    if rs:
        # RS(increments) + AG(new shards) + late wire AR (+ p2 round):
        # the sketch payload crosses twice (scatter down, gather back),
        # zero-padded so the W-way scatter tiles evenly
        w = run.dp_workers
        padded = -(-(sketch_bytes // 4) // w) * w * 4
        return _plan("rs_overlap" if overlap else "rs_fused",
                     2 * padded + grad_bytes + 16,
                     ar=1 + cs_p2, rs=1, ag=1)
    if fused:
        # ONE flat psum: increments + grad wire + 3 scalars + counter
        return _plan("fused", sketch_bytes + grad_bytes + 16,
                     ar=1 + cs_p2, p2_overlap=p2o)
    if overlap:
        # early sketch psum + late wire psum (+ optional p2 round)
        return _plan("overlap", sketch_bytes + grad_bytes + 16,
                     ar=2 + cs_p2, p2_overlap=p2o)
    # per_node reference layout: 3 psums (x/y/z) per node-stack entry
    # inside the forward, 3 scalar pmeans, and the grad wire — one
    # table psum under countsketch, else a dense pmean per param leaf
    grad_colls = (1 + cs_p2) if cs else num_leaves
    return _plan("per_node", sketch_bytes + grad_bytes + 12,
                 ar=3 * n_entries + 3 + grad_colls)


def make_eval_step(cfg: ArchConfig, run: RunConfig):
    def eval_step(params, batch):
        # mode="eval": full-sequence forward like train, but no remat
        # wrapper and — critically — no EMA sketch-state updates, so
        # evaluation can never perturb the gradient monitor
        out = forward(params, batch["tokens"], cfg=cfg, mode="eval")
        return cross_entropy(out["logits"], batch["labels"])
    return eval_step


def make_dp_train_step(cfg: ArchConfig, run: RunConfig, mesh):
    """The real multi-worker step: shard_map over `run.dp_axis_name`
    with the train state replicated and the batch split on its leading
    axis.

    Collective layout per `run.dp_collective` (DESIGN.md §9):

      * "fused" (default): ONE flat-segment psum per step carries every
        sketch node's local EMA increments, the gradient wire (the
        count-sketch table — int8-grid values under wire_dtype="int8" —
        or the dense grads), the scalar metrics, and a worker counter.
        Only the optional countsketch p2 round adds a second, O(p2*k)
        collective. Sketched-backprop consumption reads the previous
        step's merged triples (one-step lag); monitoring-only sketches
        are bitwise identical to per_node.
      * "per_node": the PR 3 reference layout — with sketching enabled,
        an O(d*k) psum per node per layer inside the forward (DP-EXACT
        consumption of the current step's full-batch sketch, DESIGN.md
        §4), plus the per-leaf dense pmean or table psum for grads.
      * "overlap": the two-phase schedule (DESIGN.md §10) — for
        sketched-backprop trees, the sketch-increment flat psum is
        issued right after the forward (barrier-pinned, hideable
        behind the backward sweep) and the merged triple is folded in
        BEFORE sketched_matmul's backward consumes it: current-step
        DP-exact consumption, bitwise equal to per_node with TWO
        all-reduces per step. Monitor-mode trees (no consumer) keep
        the fused single-collective fast path.

    `run.dp_axis_name` may be a TUPLE of mesh axes — the dp supergroup
    of a TP×DP×pod mesh (e.g. ("pod", "data") on the production 3D
    mesh): the batch splits over the flattened group and every dp
    collective takes the tuple directly. Under
    `run.dp_merge="reduce_scatter"` (DESIGN.md §12) the step further
    keeps only this worker's shard of the merged sketch state — see
    the rs branch in `make_train_step`.

    Params/optimizer moments stay identical on every replica (the
    update is computed from merged quantities only); the countsketch
    error-feedback accumulators — which under the int8 wire also carry
    each worker's quantization residual — and the rs sketch shards are
    INTENTIONALLY per-worker (device-local buffers under the
    replicated out-spec; train/loop.py checkpoints them per worker via
    `checkpoint.checkpointer.gather_per_worker` so the decomposition
    survives restarts)."""
    import dataclasses

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    run = finalize_run(cfg, run)
    ax = run.dp_axis_name
    if ax is not None and run.sketch.enabled and \
            run.dp_collective == "per_node" and \
            run.sketch.dp_axis is None:
        run = dataclasses.replace(
            run, sketch=dataclasses.replace(run.sketch, dp_axis=ax))
    # (fused mode needs no settings surgery here: make_train_step flips
    # the forward to deferred-increment emission itself)
    members = ax if isinstance(ax, tuple) else \
        (ax,) if ax is not None else ()
    if not members or any(a not in mesh.axis_names for a in members):
        raise ValueError(
            f"make_dp_train_step needs run.dp_axis_name naming mesh "
            f"axes; got {ax!r} for mesh axes {mesh.axis_names}")
    workers = 1
    for a in members:
        workers *= mesh.shape[a]
    if run.global_batch % workers:
        raise ValueError(
            f"global_batch={run.global_batch} not divisible by the "
            f"{workers}-way {ax!r} axis")
    if run.sketch.enabled and run.dp_workers != workers:
        raise ValueError(
            f"run.dp_workers={run.dp_workers} but the {ax!r} axis is "
            f"{workers}-way: the EMA sketch projections are sized for "
            f"the per-worker token count — set dp_workers={workers} in "
            f"RunConfig (or disable sketching)")
    step = make_train_step(cfg, run)
    return shard_map(step, mesh=mesh,
                     in_specs=(P(), P(ax)), out_specs=(P(), P()),
                     check_vma=False)
