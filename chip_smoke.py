#!/usr/bin/env python3
"""Bring-up smoke test of the sketched trainer on TPU v5e chips.

Drives the LM training path (`run_training` -> `make_train_step` with
EMA-sketched backprop), the fused sketch-update kernel and the serving
engine through their normal entry points, at stablelm-12b's published
widths cut to one chip: 2 layers (the pattern's period is 1), a 1/8
vocabulary slice, sequence 2048, batch 1, weights made from ``--seed``.

    python chip_smoke.py               # one chip: train, sketch-off
                                       # reference, kernel, serve
    python chip_smoke.py --four-chips  # four chips: the data-parallel
                                       # and tensor-parallel steps, each
                                       # against its one-chip reference

Each phase prints one line. Every check raises on failure, so a failed
phase ends the script with a non-zero exit before the last line, which
on success is one JSON object naming the device. Times printed are
bring-up observations of one run, not benchmark results. Without a TPU
the script exits non-zero at once: it never carries on on the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.core.reconstruct import reconstruct  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.transformer import (  # noqa: E402
    SketchSettings, forward, init_params,
)
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.sketches import ema_triple_update  # noqa: E402
from repro.telemetry import attention_paths, compile_counter  # noqa: E402
from repro.train.loop import LoopConfig, run_training  # noqa: E402
from repro.train.state import RunConfig  # noqa: E402

PLATFORM = "tpu"
ARCH = "stablelm-12b"
LAYERS = 2
VOCAB = 12544                  # 100352 / 8
SEQ = 2048
BATCH = 1
STEPS = 5
K_MAX = 17
KERNEL_WIDTHS = (5120, 13824)  # d_model and d_ff: the cut's node widths
SERVE_PROMPTS, SERVE_PROMPT_LEN, SERVE_NEW = 4, 512, 8

# Step-0 loss band around ln(VOCAB). The head is N(0, 1/d) and its input
# is RMS-normalised, so the logits start with variance ~1 and the
# expected cross-entropy is ln V + 1/2; the band leaves 1 nat above that
# and 1/2 below ln V.
LOSS_BAND = (-0.5, 1.5)
# Sketch-on vs sketch-off step-0 loss, and the same comparison across
# data- and tensor-parallel layouts: sketching does not touch the
# forward, but separately compiled programs may fuse the bf16 forward
# differently. One bf16 rounding (2^-8 relative) in every logit moves
# the mean cross-entropy by at most ~2^-8 x logit RMS (~1) = 4e-3.
LOSS_TOL = 1e-2
# Relative (normwise) gaps of bf16 results computed by two different
# programs: 16 unit roundoffs of bf16 (16 x 2^-9).
BF16_REL_TOL = 2.0 ** -5
# Fused kernel vs the jnp update at default precision: on the TPU either
# may round its f32 operands to bf16 (2^-9 each) before the MXU; the
# sums over T keep that relative size. |got - want| <= tol (rms + |want|).
KERNEL_TOL = 2.0 ** -5

def say(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def require_tpu(n: int) -> list:
    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        raise SystemExit(
            f"chip_smoke: no TPU found — JAX sees {len(devs)} "
            f"{devs[0].platform} device(s); this script runs only on a "
            f"TPU")
    if len(devs) < n:
        raise SystemExit(f"chip_smoke: needs {n} TPU chips, found "
                         f"{len(devs)}")
    return devs[:n]


def cut_config():
    return dataclasses.replace(get_arch(ARCH), num_layers=LAYERS,
                               vocab_size=VOCAB)


def run_config(sketch: bool, *, batch: int = BATCH,
               dp_axis: str | None = None, dp_workers: int = 1):
    """The RunConfig `launch/train.py` builds for these flags."""
    return RunConfig(
        seq_len=SEQ, global_batch=batch,
        optimizer=AdamWConfig(lr=3e-4),
        warmup_steps=min(20, STEPS // 5 + 1), total_steps=STEPS,
        sketch=SketchSettings(enabled=sketch, k_max=K_MAX),
        dp_axis_name=dp_axis, dp_workers=dp_workers)


def train(cfg, run, steps: int, *, seed: int, dp_mesh=None):
    """`run_training` in a fresh checkpoint directory outside the
    checkout, writing no checkpoint. Returns (state, history, programs
    lowered after step 0, from the loop's per-step `compiles`)."""
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
        loop = LoopConfig(num_steps=steps, ckpt_every=0, ckpt_dir=d,
                          log_every=1)
        state, hist = run_training(cfg, run, loop, seed=seed,
                                   dp_mesh=dp_mesh)
    check(len(hist) == steps, f"ran {len(hist)} of {steps} steps")
    return state, hist, sum(h["compiles"] for h in hist[1:])


def check_history(hist, tag: str) -> None:
    for h in hist:
        check(math.isfinite(h["loss"]) and math.isfinite(h["grad_norm"]),
              f"{tag}: non-finite loss/grad_norm at step {h['step']}: "
              f"{h['loss']} {h['grad_norm']}")
        check(h["skipped_total"] == 0,
              f"{tag}: the NaN guard skipped step {h['step']}")


def on_platform(tree) -> bool:
    return all(d.platform == PLATFORM for leaf in jax.tree.leaves(tree)
               for d in leaf.devices())


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def hbm_bytes(dev) -> int | None:
    stats = dev.memory_stats()
    return None if stats is None else stats.get("bytes_limit")


def rel_gap(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------


def phase_train(cfg, counter, seed):
    run = run_config(True)
    t0 = time.perf_counter()
    state, hist, late = train(cfg, run, STEPS, seed=seed)
    wall = time.perf_counter() - t0
    say("train", arch=ARCH, layers=cfg.num_layers, d_model=cfg.d_model,
        heads=f"{cfg.num_heads}/{cfg.num_kv_heads}x"
              f"{cfg.resolved_head_dim}",
        d_ff=cfg.d_ff, vocab=cfg.vocab_size,
        params=cfg.param_count(), seq=SEQ, batch=BATCH, k_max=K_MAX,
        remat=cfg.remat_policy)
    check_history(hist, "train")
    ln_v = math.log(cfg.vocab_size)
    loss0 = hist[0]["loss"]
    check(ln_v + LOSS_BAND[0] <= loss0 <= ln_v + LOSS_BAND[1],
          f"step-0 loss {loss0} outside [ln V{LOSS_BAND[0]:+}, "
          f"ln V{LOSS_BAND[1]:+}] with ln V = {ln_v}")
    check(late == 0, f"{late} compilation(s) after the first step")
    paths = attention_paths()
    check(PLATFORM != "tpu" or (paths["kernel"] > 0 and paths["scan"] == 0),
          f"the train step's attention did not trace the kernel only: "
          f"{paths}")
    check(on_platform(state), "train state is not on the TPU")
    dev = jax.devices()[0]
    peak, hbm = peak_bytes(dev), hbm_bytes(dev)
    check(peak is None or hbm is None or peak < hbm,
          f"peak_bytes_in_use {peak} >= HBM {hbm}")
    say("train", losses=[h["loss"] for h in hist],
        grad_norms=[h["grad_norm"] for h in hist],
        ln_vocab=ln_v)
    say("train", note="bring-up observation, not a benchmark",
        wall_s=wall, first_step_s=hist[0]["time_s"],
        backend_compile_s=counter.compile_s,
        programs_lowered=counter.lowerings,
        step_s_from_step2=[h["time_s"] for h in hist[2:]],
        compilations_after_step0=late, peak_bytes_in_use=peak,
        hbm_bytes_limit=hbm, attention_paths=paths)
    # one node's triple for the reconstruction precision check
    name = sorted(state.sketch.nodes)[0]
    node = state.sketch.nodes[name]
    probe = {"name": name, "x": node.x[0], "y": node.y[0], "z": node.z[0],
             "omega": state.sketch.proj["omega"],
             "k_active": state.sketch.k_active}
    return loss0, probe


def phase_sketch_off(cfg, seed, loss_on):
    _, hist, _ = train(cfg, run_config(False), 1, seed=seed)
    check_history(hist, "sketch-off")
    loss_off = hist[0]["loss"]
    gap = abs(loss_on - loss_off)
    say("sketch-off", step0_loss_on=loss_on, step0_loss_off=loss_off,
        abs_gap=gap, tol=LOSS_TOL)
    check(gap <= LOSS_TOL,
          f"sketch-on step-0 loss {loss_on} != sketch-off {loss_off}")


def phase_kernel(probe, seed):
    T, k, beta = SEQ, K_MAX, 0.95
    ka = jnp.asarray(13, jnp.int32)
    for d in KERNEL_WIDTHS:
        ks = jax.random.split(jax.random.PRNGKey(seed + d), 8)
        a = jax.random.normal(ks[0], (T, d), jnp.bfloat16)
        xs, ys, zs = (0.1 * jax.random.normal(ks[i], (d, k))
                      for i in (1, 2, 3))
        ups, omg, phi = (jax.random.normal(ks[i], (T, k))
                         for i in (4, 5, 6))
        psi = jax.random.normal(ks[7], (k,))
        args = (xs, ys, zs, a, ups, omg, phi, psi)

        def upd(use_kernel):
            return jax.jit(lambda *xs_: ema_triple_update(
                *xs_, beta=beta, k_active=ka, use_kernel=use_kernel))

        kern = upd(True)
        hlo = kern.lower(*args).compile().as_text()
        check("tpu_custom_call" in hlo,
              f"d={d}: no tpu_custom_call in the compiled kernel update")
        got = kern(*args)
        want = upd(False)(*args)
        with jax.default_matmul_precision("highest"):
            exact = upd(False)(*args)
        worst = 0.0
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            rms = float(np.sqrt(np.mean(w * w)))
            worst = max(worst, float(np.max(np.abs(g - w) / (rms + np.abs(w)))))
        say("kernel", T=T, d=d, k=k, tpu_custom_call=True,
            err_vs_jnp=worst, tol=KERNEL_TOL,
            kernel_rel_vs_highest=max(rel_gap(g, e)
                                      for g, e in zip(got, exact)),
            jnp_rel_vs_highest=max(rel_gap(w, e)
                                   for w, e in zip(want, exact)))
        check(worst <= KERNEL_TOL,
              f"d={d}: fused kernel differs from the jnp update by "
              f"{worst} > {KERNEL_TOL}")

    def rec(x, y, z, om, kact):
        r = reconstruct(x, y, z, om, kact, mode="fast")
        with jax.default_matmul_precision("highest"):
            return r.dense()

    args = (probe["x"], probe["y"], probe["z"], probe["omega"],
            probe["k_active"])
    default = jax.jit(rec)(*args)
    with jax.default_matmul_precision("highest"):
        highest = jax.jit(rec)(*args)
    say("kernel", reconstruct_node=probe["name"],
        reconstruct_rel_gap_default_vs_highest=rel_gap(default, highest),
        note="observation only")


def phase_serve(cfg, seed):
    from repro.serve import ServeEngine

    params = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), init_params(key, cfg)))(
            jax.random.PRNGKey(seed))
    prompts = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (SERVE_PROMPTS, SERVE_PROMPT_LEN),
        0, cfg.vocab_size, jnp.int32)
    eng = ServeEngine(cfg=cfg, params=params,
                      max_context=SERVE_PROMPT_LEN + SERVE_NEW,
                      monitor=True)
    t0 = time.perf_counter()
    tok0 = eng.start(prompts)
    jax.block_until_ready(tok0)
    t_prefill = time.perf_counter() - t0
    eng.decode_step()
    first = np.asarray(eng.last_logits[:, -1], np.float32)
    t0 = time.perf_counter()
    for _ in range(SERVE_NEW - 2):
        tok = eng.decode_step()
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0
    seq = jnp.concatenate([prompts, tok0[:, None]], axis=1)
    full = jax.jit(lambda p, t: forward(p, t, cfg=cfg, mode="eval")
                   ["logits"][:, -1])(params, seq)
    full = np.asarray(full, np.float32)
    check(np.isfinite(first).all(), "non-finite decode logits")
    gap = rel_gap(first, full)
    mon = eng._slots["mon"]
    rec = eng.telemetry_record()
    say("serve", prompts=SERVE_PROMPTS, prompt_len=SERVE_PROMPT_LEN,
        decode_steps=SERVE_NEW - 1, params_dtype="bf16", monitor=True,
        decode_vs_full_rel_gap=gap, tol=BF16_REL_TOL,
        argmax_agree=float(np.mean(first.argmax(-1) == full.argmax(-1))),
        monitor_readings=int(mon.ring.count), flags=sorted(rec.flags))
    say("serve", note="bring-up observation, not a benchmark",
        prefill_s_with_compile=t_prefill,
        decode_s_for_steps_3_on=t_decode)
    check(gap <= BF16_REL_TOL,
          f"first decode logits differ from the full forward by {gap}")
    check(int(mon.ring.count) == SERVE_NEW,
          f"monitor saw {int(mon.ring.count)} of {SERVE_NEW} steps")
    check(on_platform(mon), "serve monitor state is not on the TPU")


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------


def device_bytes(tree) -> list[int]:
    """Bytes of `tree` held on each device."""
    per: dict = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    return [per[i] for i in sorted(per)]


def phase_dp(cfg, devs, seed):
    """`launch/train.py --dp 4`: one step of the shard_map data-parallel
    step (fused layout, sketch on) against the four batch shards
    accumulated in sequence on one chip (DESIGN.md §4)."""
    from jax.sharding import Mesh

    from repro.data.pipeline import PipelineConfig, host_batch
    from repro.train.step import cross_entropy
    from repro.train.state import init_train_state

    W = len(devs)
    run = run_config(True, batch=W, dp_axis="data", dp_workers=W)
    mesh = Mesh(np.array(devs), ("data",))
    state, hist, _ = train(cfg, run, 1, seed=seed,
                           dp_mesh=mesh)
    check_history(hist, "dp")
    held = device_bytes(state)
    peaks = [peak_bytes(d) for d in devs]
    got = {n: [np.asarray(getattr(v, f)) for f in "xyz"]
           for n, v in state.sketch.nodes.items()}
    loss_dp = hist[0]["loss"]
    del state

    tokens, labels = host_batch(
        PipelineConfig(seed=seed, global_batch=W, seq_len=SEQ,
                       vocab=cfg.vocab_size), 0)
    st0 = jax.jit(lambda k: init_train_state(k, cfg, run))(
        jax.random.PRNGKey(seed))
    settings = run.sketch

    @jax.jit
    def shard_fwd(params, sketch, tok, lab):
        out = forward(params, tok, cfg=cfg, mode="train",
                      sketch_state=sketch, settings=settings)
        loss = cross_entropy(out["logits"], lab, run.z_weight) + \
            run.aux_weight * out["aux"]
        return loss, {
            n: (v.x, v.y, v.z) for n, v in out["sketch_state"].nodes.items()}

    losses, want = [], None
    for w in range(W):
        lw, inc = shard_fwd(st0.params, st0.sketch, tokens[w:w + 1],
                            labels[w:w + 1])
        losses.append(float(lw))
        want = inc if want is None else jax.tree.map(jnp.add, want, inc)
    del st0
    loss_ref = float(np.mean(losses))
    gap = max(rel_gap(got[n][i], want[n][i])
              for n in got for i in range(3))
    say("dp", workers=W, layout=run.dp_collective, loss_dp=loss_dp,
        loss_sequential=loss_ref, loss_abs_gap=abs(loss_dp - loss_ref),
        loss_tol=LOSS_TOL, sketch_rel_gap=gap, sketch_tol=BF16_REL_TOL)
    say("dp", state_bytes_per_device=held, peak_bytes_in_use=peaks)
    check(abs(loss_dp - loss_ref) <= LOSS_TOL,
          f"DP loss {loss_dp} != sequential {loss_ref}")
    check(gap <= BF16_REL_TOL,
          f"DP merged sketches differ from the sequential sum by {gap}")
    check(min(held) > 0 and max(held) <= 1.01 * min(held),
          f"DP state is not replicated evenly: {held}")


def phase_tp(cfg, devs, seed):
    """The tensor-parallel mesh step (`run_training_sharded`) on a
    (data 1, model W) mesh against the unsharded one-chip step."""
    from repro.launch.mesh import make_mesh, rules_for_mesh
    from repro.train.loop import run_training_sharded

    run = run_config(True)
    steps = 2
    ref_state, ref, _ = train(cfg, run, steps, seed=seed)
    del ref_state
    check_history(ref, "unsharded")
    mesh = make_mesh(1, len(devs))
    rules = rules_for_mesh(mesh)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
        loop = LoopConfig(num_steps=steps, ckpt_every=0, ckpt_dir=d,
                          log_every=1)
        state, hist = run_training_sharded(cfg, run, loop, mesh, rules,
                                           seed=seed)
    check_history(hist, "tp")
    held = device_bytes(state)
    check(on_platform(state), "TP state is not on the TPU")
    rows = []
    for a, b in zip(hist, ref):
        rows.append({"step": a["step"], "loss_tp": a["loss"],
                     "loss_1chip": b["loss"],
                     "grad_norm_tp": a["grad_norm"],
                     "grad_norm_1chip": b["grad_norm"]})
    say("tp", mesh=dict(mesh.shape), steps=rows, loss_tol=LOSS_TOL,
        grad_norm_rel_tol=BF16_REL_TOL)
    say("tp", state_bytes_per_device=held,
        peak_bytes_in_use=[peak_bytes(d) for d in devs])
    for r in rows:
        check(abs(r["loss_tp"] - r["loss_1chip"]) <= LOSS_TOL,
              f"TP loss differs from the unsharded step: {r}")
        check(abs(r["grad_norm_tp"] - r["grad_norm_1chip"])
              <= BF16_REL_TOL * r["grad_norm_1chip"],
              f"TP grad norm differs from the unsharded step: {r}")
    check(max(held) <= 0.5 * sum(held),
          f"TP state is not spread over the mesh: {held}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data- and tensor-parallel steps "
                         "on four chips, each against its one-chip "
                         "reference")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, data and kernel inputs")
    args = ap.parse_args(argv)

    n = 4 if args.four_chips else 1
    devs = require_tpu(n)
    say("device", platform=devs[0].platform, kind=devs[0].device_kind,
        count=n, jax=jax.__version__,
        compile_cache=enable_compile_cache())
    counter = compile_counter()
    cfg = cut_config()
    if args.four_chips:
        phase_dp(cfg, devs, args.seed)
        phase_tp(cfg, devs, args.seed)
    else:
        loss0, probe = phase_train(cfg, counter, args.seed)
        phase_sketch_off(cfg, args.seed, loss0)
        phase_kernel(probe, args.seed)
        phase_serve(cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": n}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
