"""Distributed training launcher.

Composes mesh + sharding rules + sharded train state + the fault-
tolerant loop. With more than one device (or ``--tp M``) the step runs
on a (devices/M data, M model) mesh built from the devices present —
M defaults to all of them; on the CPU, fake devices come from XLA_FLAGS:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.train \
        --arch tinyllama-1.1b --reduced --tp 4 --steps 20

Data-parallel shard_map with count-sketch gradient compression (the
only cross-worker traffic is the O(r*c) sketch table + optional p2
value round; replicated state stays in sync — only the error-feedback
residuals are per-worker, merged mass-exactly at checkpoint time):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.train \
        --arch tinyllama-1.1b --reduced --dp 4 --compress countsketch \
        --cs-p2 2 --steps 20

Sketching beyond the dense LM (DESIGN.md §15) needs no extra flags —
the NodeSpec registry (`sketches.registry.node_specs_for`) resolves the
arch's node families, so MoE (per-expert nodes, expert-axis sharded)
and recurrent archs (mLSTM / RG-LRU carry nodes) launch identically:

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen3-moe-30b-a3b --reduced --dp 4 --steps 20
    PYTHONPATH=src python -m repro.launch.train \
        --arch recurrentgemma-2b --reduced --proj-kind psparse --steps 20

Fault tolerance: checkpoint/restart + straggler watchdog + NaN rewind
live in train/loop.py; elastic restarts (different mesh) reshard through
checkpoint/checkpointer.py.
"""
import argparse
import dataclasses
import logging

import jax

from repro.configs import SHAPES, get_arch, reduced as reduce_cfg
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import host_mesh, rules_for_mesh
from repro.models.transformer import SketchSettings
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import use_rules
from repro.train.loop import LoopConfig, run_training_sharded
from repro.train.state import ConfigError, RunConfig

logging.basicConfig(level=logging.INFO,
                    format="%(asctime)s %(name)s %(message)s")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--shape", default=None,
                    help="assigned shape name (overrides seq/batch)")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-runnable reduced config")
    ap.add_argument("--tp", type=int, default=0, metavar="M",
                    help="(devices/M, M) data x model mesh over every "
                         "device present (default with >1 device: M = "
                         "all devices)")
    ap.add_argument("--dp", type=int, default=0, metavar="W",
                    help="W-way data-parallel shard_map step (needs W "
                         "devices; batch must divide by W)")
    ap.add_argument("--dp-pods", type=int, default=0, metavar="P",
                    help="split the --dp workers over a (P, W/P) pod x "
                         "data mesh: dp collectives take the flattened "
                         "('pod','data') supergroup (needs P | W)")
    ap.add_argument("--dp-merge", default="psum",
                    choices=["psum", "reduce_scatter"],
                    help="DP sketch-state merge: 'psum' = every worker "
                         "holds the full merged NodeTree; "
                         "'reduce_scatter' = ZeRO-style — each worker "
                         "owns 1/W of the merged triple buffer, one "
                         "all-gather rebuilds it for its consumers, "
                         "and checkpoints keep per-worker shards "
                         "(DESIGN.md 12)")
    ap.add_argument("--compress", default="none",
                    choices=["none", "topk", "countsketch"],
                    help="DP gradient compression mode")
    ap.add_argument("--cs-p2", type=int, default=0,
                    help="countsketch second-round candidate multiplier "
                         "(SketchedSGD p2; 0 disables)")
    ap.add_argument("--wire-dtype", default="fp32",
                    choices=["fp32", "int8"],
                    help="DP wire precision end-to-end (DESIGN.md "
                         "14): int8 quantizes BOTH the count-sketch "
                         "table (per-row grid, residual in the "
                         "error-feedback buffer) and the EMA sketch "
                         "increment segments (residual in the "
                         "per-worker sketch_err ledger, mass "
                         "catch-up on the next step)")
    ap.add_argument("--ring-wire", action="store_true",
                    help="route the flat-segment DP merge through the "
                         "Pallas remote-DMA ring all-reduce "
                         "(kernels/ring_allreduce.py) instead of "
                         "psum: bitwise-identical for fp32; with "
                         "--wire-dtype int8 the sketch segments ride "
                         "the quantization-aware int8 ring (requant "
                         "per hop, residual ledger into sketch_err) "
                         "while counters/scalars/table stay on an "
                         "exempt f32 psum (DESIGN.md 14)")
    ap.add_argument("--dp-collective", default="fused",
                    choices=["fused", "per_node", "overlap"],
                    help="DP collective layout: 'fused' = ONE flat "
                         "psum per step (sketch increments + gradient "
                         "wire + metrics; sketched-backprop consumes "
                         "the previous step's merge), 'overlap' = "
                         "two-phase schedule (sketch psum issued after "
                         "the forward and hidden behind the backward; "
                         "consumption is current-step DP-exact, no "
                         "lag), 'per_node' = PR 3 reference (one psum "
                         "per sketch node per layer)")
    ap.add_argument("--strategy", default="megatron",
                    choices=["megatron", "fsdp"])
    ap.add_argument("--no-sketch", action="store_true")
    ap.add_argument("--proj-kind", default="gaussian",
                    choices=["gaussian", "psparse"],
                    help="sketch projection family: 'gaussian' = dense "
                         "(T, k_max) matrices; 'psparse' = seeds-only "
                         "p-sparsified projections regenerated on the "
                         "fly (O(1) projection memory, memory-bound "
                         "update; DESIGN.md 13)")
    ap.add_argument("--proj-density", type=float, default=0.1,
                    help="psparse nonzero fraction p (support rows "
                         "m = max(k_max, round(p*T)))")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt_launch")
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="checkpoint period in steps; 0 writes none")
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    seq, batch = args.seq_len, args.batch
    if args.shape:
        sh = SHAPES[args.shape]
        seq, batch = sh.seq_len, sh.global_batch

    compression = None
    if args.compress != "none":
        from repro.optim.compression import CompressionConfig
        compression = CompressionConfig(mode=args.compress,
                                        cs_p2=args.cs_p2,
                                        wire_dtype=args.wire_dtype)
    if args.dp_pods:
        if not args.dp or args.dp % args.dp_pods:
            raise SystemExit(
                f"--dp-pods {args.dp_pods} must divide --dp {args.dp}")
    dp_axis = None
    if args.dp:
        dp_axis = ("pod", "data") if args.dp_pods else "data"
    try:
        run = RunConfig(
            seq_len=seq, global_batch=batch,
            optimizer=AdamWConfig(lr=args.lr),
            warmup_steps=min(20, args.steps // 5 + 1),
            total_steps=args.steps,
            sketch=SketchSettings(enabled=not args.no_sketch, k_max=17,
                                  proj_kind=args.proj_kind,
                                  proj_density=args.proj_density),
            compression=compression,
            dp_axis_name=dp_axis,
            dp_workers=args.dp if args.dp else 1,
            dp_collective=args.dp_collective,
            dp_merge=args.dp_merge,
            # --wire-dtype int8 means int8 END-TO-END: sketch increments
            # (here) and the cs table (CompressionConfig above). The
            # sketch wire only quantizes a cross-worker exchange, so it
            # stays fp32 without a dp axis / under per_node.
            sketch_wire_dtype=args.wire_dtype if (
                dp_axis is not None and not args.no_sketch and
                args.dp_collective != "per_node" and
                args.dp_merge == "psum") else "fp32",
            ring_wire=args.ring_wire,
        )
    except ConfigError as e:
        # the RunConfig compatibility matrix rejected the flag
        # combination — one structured error naming the two conflicting
        # fields (train/state.py, DESIGN.md §15)
        raise SystemExit(f"invalid flag combination: {e}")
    enable_compile_cache()
    loop = LoopConfig(num_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir, log_every=10)

    if args.dp:
        import numpy as np
        from jax.sharding import Mesh

        if len(jax.devices()) < args.dp:
            raise SystemExit(
                f"--dp {args.dp} needs {args.dp} devices, have "
                f"{len(jax.devices())} (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={args.dp})")
        devs = np.array(jax.devices()[:args.dp])
        if args.dp_pods:
            mesh = Mesh(devs.reshape(args.dp_pods, -1),
                        ("pod", "data"))
        else:
            mesh = Mesh(devs, ("data",))
        from repro.train.loop import run_training
        state, hist = run_training(cfg, run, loop, dp_mesh=mesh)
    elif args.tp or len(jax.devices()) > 1:
        mesh = host_mesh(args.tp or None)
        rules = rules_for_mesh(mesh, strategy=args.strategy)
        state, hist = run_training_sharded(cfg, run, loop, mesh, rules)
    else:
        from repro.train.loop import run_training
        state, hist = run_training(cfg, run, loop)
    print(f"done: {len(hist)} steps, final loss "
          f"{hist[-1]['loss']:.4f}, skipped {int(state.skipped)}")


if __name__ == "__main__":
    main()
