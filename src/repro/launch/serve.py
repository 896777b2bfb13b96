"""Distributed serving launcher: mesh-aware batched generation.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve \
        --arch tinyllama-1.1b --reduced --tp 4 \
        --num-prompts 4 --max-new 8

``--tp M`` serves from a (devices/M data, M model) mesh over every
device present; without it the engine runs on one device.

Live monitoring (DESIGN.md §11): ``--monitor`` threads activation
sketches through the jitted serve steps and prints pathology flags;
``--telemetry-json PATH`` exports the run as schema-versioned JSONL.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache
from repro.configs import get_arch, reduced as reduce_cfg
from repro.launch.mesh import host_mesh, rules_for_mesh
from repro.models.transformer import init_params
from repro.parallel.sharding import param_shardings, use_rules
from repro.serve.engine import ServeEngine
from repro.telemetry import TelemetryLog


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--tp", type=int, default=0, metavar="M",
                    help="serve from a (devices/M, M) data x model mesh "
                         "over every device present (0: one device)")
    ap.add_argument("--num-prompts", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-context", type=int, default=64)
    ap.add_argument("--monitor", action="store_true",
                    help="live activation sketches in the serve steps")
    ap.add_argument("--monitor-rank", type=int, default=4)
    ap.add_argument("--telemetry-json", default=None, metavar="PATH",
                    help="export TelemetryRecords as JSONL")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    key = jax.random.PRNGKey(0)
    prompts = jax.random.randint(
        key, (args.num_prompts, args.prompt_len), 0, cfg.vocab_size)

    tlog = TelemetryLog(args.telemetry_json) if args.telemetry_json \
        else None
    mk = lambda params: ServeEngine(
        cfg=cfg, params=params, max_context=args.max_context,
        monitor=args.monitor, monitor_rank=args.monitor_rank,
        telemetry_log=tlog)

    if args.tp:
        mesh = host_mesh(args.tp)
        rules = rules_for_mesh(mesh)
        with use_rules(rules), mesh:
            params = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                                  init_params(key, cfg))
            params = jax.device_put(params,
                                    param_shardings(rules, params))
            engine = mk(params)
            t0 = time.time()
            out = engine.generate(prompts, args.max_new)
            dt = time.time() - t0
    else:
        params = init_params(key, cfg)
        engine = mk(params)
        t0 = time.time()
        out = engine.generate(prompts, args.max_new)
        dt = time.time() - t0

    tput = args.num_prompts * args.max_new / dt
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({tput:.1f} tok/s incl. compile)")
    for i in range(min(2, args.num_prompts)):
        print(f"  prompt {i}: {out[i].tolist()}")

    if args.monitor:
        rec = engine.telemetry_record()
        if rec.flags:
            print("pathology flags:")
            for name, paths in sorted(rec.flags.items()):
                print(f"  {name}: {', '.join(paths)}")
        else:
            print("pathology flags: none")
    if tlog is not None:
        tlog.close()
        print(f"telemetry: {tlog.records_written} record(s) -> "
              f"{args.telemetry_json}")


if __name__ == "__main__":
    main()
