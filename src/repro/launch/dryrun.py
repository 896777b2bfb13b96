import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=512"
# ^ MUST run before any jax import: jax locks the device count on first
# init. Do not move; do not set this flag anywhere global.

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape) cell and both production meshes —
(16,16) data x model and (2,16,16) pod x data x model — lower + compile
the real step function (train_step for train shapes, prefill/decode for
serving shapes) with ShapeDtypeStruct inputs (no allocation), then record
memory_analysis / cost_analysis / HLO-derived roofline terms into
artifacts/dryrun/<arch>__<shape>__<mesh>.json (+ the compiled HLO text,
gzipped, for §Perf re-analysis).

Usage:
  python -m repro.launch.dryrun --arch granite-34b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod | --both]
"""
import argparse
import gzip
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    SHAPES, ARCHS, cell_is_runnable, get_arch, input_specs,
)
from repro.launch.hlo_analysis import analyze_hlo_text
from repro.launch.mesh import make_mesh, rules_for_mesh
from repro.models.transformer import SketchSettings, abstract_cache
from repro.parallel.sharding import (
    cache_shardings, param_shardings, use_rules,
)
from repro.serve.engine import make_decode_step, make_prefill_step
from repro.train.state import RunConfig, abstract_train_state
from repro.train.step import make_train_step

OUT_DIR = "artifacts/dryrun"


def sketch_sharding_report(state, state_shardings, rules,
                           *, min_bytes: int = 1 << 20) -> dict:
    """Resolved sketch-triple shardings, asserted non-replicated.

    Walks the NodeTree with its resolved NamedShardings and FAILS the
    dry run when any (..., d, k) triple leaf above `min_bytes` is left
    replicated on its width dim — an OOM-sized replicated sketch must
    never pass a dry run silently (DESIGN.md §12). Returns a per-leaf
    report {node/leaf: {shape, spec, shards, bytes_per_device}} that
    lands in the cell JSON so §Perf can audit the resolution."""
    sk = getattr(state, "sketch", None)
    if sk is None or not hasattr(sk, "nodes"):
        return {}
    sh = state_shardings.sketch
    report, bad = {}, []
    for name in sorted(sk.nodes):
        for leaf_name in ("x", "y", "z"):
            leaf = getattr(sk.nodes[name], leaf_name)
            spec = getattr(sh.nodes[name], leaf_name).spec
            d_ax = spec[-2] if len(spec) >= 2 else None
            members = d_ax if isinstance(d_ax, tuple) else \
                ((d_ax,) if d_ax is not None else ())
            shards = 1
            for a in members:
                shards *= rules.mesh.shape[a]
            nbytes = leaf.dtype.itemsize
            for s in leaf.shape:
                nbytes *= s
            report[f"{name}/{leaf_name}"] = {
                "shape": list(leaf.shape), "spec": str(spec),
                "shards": shards,
                "bytes_per_device": nbytes // shards,
            }
            if nbytes >= min_bytes and shards == 1:
                bad.append(f"{name}/{leaf_name} {tuple(leaf.shape)} "
                           f"spec={spec}")
    if bad:
        raise AssertionError(
            "replicated sketch state above "
            f"{min_bytes} bytes: " + "; ".join(bad))
    return report


def batch_shardings(specs: dict, rules) -> dict:
    mesh, dp = rules.mesh, rules.dp
    out = {}
    for k, v in specs.items():
        axes = [None] * len(v.shape)
        size = rules.dp_size
        if v.shape[0] % size == 0:
            axes[0] = dp
        out[k] = NamedSharding(mesh, P(*axes))
    return out


def _serving_params(cfg):
    """Inference weights are bf16 (standard serving practice; the f32
    masters live only in the training optimizer state)."""
    from repro.models.transformer import abstract_params
    params = abstract_params(cfg)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), params)


def make_run_config(cfg, shape, *, sketched: bool = True) -> RunConfig:
    st = SketchSettings(
        enabled=sketched and cfg.sketch_mode != "none",
        beta=0.95, k_max=33, recon_mode="fast", factored=True,
    )
    return RunConfig(seq_len=shape.seq_len, global_batch=shape.global_batch,
                     sketch=st)


# §Perf variant knobs: overrides applied to the ArchConfig before
# lowering (hypothesis -> change -> re-lower -> re-analyse loop).
VARIANTS: dict[str, dict] = {
    "base": {},
    # it1: no config knobs — measures the bf16-cotangent fix in
    # core/sketched_linear.py (baseline artifacts predate it)
    "it1_bf16ct": {},
    # it2: store/gather params in bf16 (f32 master copies live in the
    # optimizer state; ZeRO all-gathers + saved weights halve)
    "bf16params": {"param_dtype": jnp.bfloat16},
    # it3: full recompute — trade compute (cheap term) for residual memory
    "remat_nothing": {"remat_policy": "nothing"},
    # it4 (xlstm): chunked sLSTM — weights stream once per chunk, not per
    # timestep
    "slstm_chunk": {"slstm_chunk": 64},
    # it5: FSDP strategy — gather full per-layer WEIGHTS (100s of MB)
    # instead of full-sequence ACTIVATIONS (10s of GB) at block
    # boundaries; activations stay token-sharded end-to-end
    "fsdp": {"_strategy": "fsdp"},
    # combined best-known configuration
    "best": {"_strategy": "fsdp", "slstm_chunk": 64},
}


def variant_strategy(variant: str) -> str:
    return VARIANTS[variant].get("_strategy", "megatron")


def build_cell(cfg, shape, rules, *, sketched: bool = True,
               variant: str = "base"):
    """Returns (fn, args, in_shardings, donate) ready to lower."""
    import dataclasses as _dc
    knobs = dict(VARIANTS[variant])
    knobs.pop("_strategy", None)         # consumed by run_cell
    if "slstm_chunk" in knobs and "slstm" not in cfg.pattern:
        knobs.pop("slstm_chunk")
    if knobs:
        cfg = _dc.replace(cfg, **knobs)
    specs = input_specs(cfg, SHAPES[shape.name] if isinstance(shape, str)
                        else shape)
    if shape.kind == "train":
        run = make_run_config(cfg, shape, sketched=sketched)
        state = abstract_train_state(cfg, run)
        st_sh = param_shardings(rules, state)
        b_sh = batch_shardings(specs, rules)
        fn = make_train_step(cfg, run)
        return fn, (state, specs), (st_sh, b_sh), (0,)
    if shape.kind == "prefill":
        params = _serving_params(cfg)
        p_sh = param_shardings(rules, params)
        b_sh = batch_shardings(specs, rules)
        fn = make_prefill_step(cfg, shape.seq_len)
        return fn, (params, specs["tokens"]), (p_sh, b_sh["tokens"]), ()
    # decode
    params = _serving_params(cfg)
    p_sh = param_shardings(rules, params)
    cache = abstract_cache(cfg, shape.global_batch, shape.seq_len)
    c_sh = cache_shardings(cache, cfg, rules)
    b_sh = batch_shardings(specs, rules)
    fn = make_decode_step(cfg, shape.seq_len)
    args = (params, cache, specs["tokens"], specs["positions"])
    shs = (p_sh, c_sh, b_sh["tokens"], b_sh["positions"])
    return fn, args, shs, (1,)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             *, save_hlo: bool = True, sketched: bool = True,
             variant: str = "base", out_dir: str = OUT_DIR) -> dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "variant": variant, "sketched": sketched}
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    try:
        mesh = make_mesh(16, 16, n_pod=2 if multi_pod else 1)
        rules = rules_for_mesh(mesh, strategy=variant_strategy(variant))
        with use_rules(rules), mesh:
            fn, args, shardings, donate = build_cell(
                cfg, shape, rules, sketched=sketched, variant=variant)
            if shape.kind == "train":
                rec["sketch_sharding"] = sketch_sharding_report(
                    args[0], shardings[0], rules)
            t0 = time.time()
            lowered = jax.jit(
                fn, in_shardings=shardings, donate_argnums=donate,
            ).lower(*args)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0
        rec["status"] = "ok"
        rec["lower_s"] = round(t_lower, 2)
        rec["compile_s"] = round(t_compile, 2)
        try:
            ma = compiled.memory_analysis()
            rec["memory"] = {
                k: int(getattr(ma, k)) for k in (
                    "temp_size_in_bytes", "argument_size_in_bytes",
                    "output_size_in_bytes", "generated_code_size_in_bytes",
                    "alias_size_in_bytes")
                if hasattr(ma, k)}
        except Exception as e:  # noqa: BLE001
            rec["memory"] = {"error": str(e)}
        try:
            ca = compiled.cost_analysis()
            rec["cost_analysis"] = {
                k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    "flops" in k or "bytes" in k or k in ("transcendentals",))
            }
        except Exception as e:  # noqa: BLE001
            rec["cost_analysis"] = {"error": str(e)}
        text = compiled.as_text()
        rec["hlo"] = analyze_hlo_text(text, default_trip=cfg.num_groups)
        if save_hlo:
            os.makedirs(out_dir, exist_ok=True)
            with gzip.open(_path(out_dir, rec) + ".hlo.gz", "wt") as f:
                f.write(text)
    except Exception as e:  # noqa: BLE001
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def _path(out_dir: str, rec: dict) -> str:
    v = "" if rec.get("variant", "base") == "base" \
        else f"__{rec['variant']}"
    return os.path.join(
        out_dir, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{v}")


def save(rec: dict, out_dir: str = OUT_DIR):
    os.makedirs(out_dir, exist_ok=True)
    with open(_path(out_dir, rec) + ".json", "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true")
    ap.add_argument("--no-sketch", action="store_true")
    ap.add_argument("--variant", default="base")
    ap.add_argument("--no-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    archs = ARCHS if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None \
        else [args.shape]
    pods = [False, True] if args.both else [args.multi_pod]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                stem = _path(args.out, {
                    "arch": arch, "shape": shape, "variant": args.variant,
                    "mesh": "pod2x16x16" if mp else "pod16x16"})
                if args.skip_existing and os.path.exists(stem + ".json"):
                    print(f"[skip existing] {stem}")
                    continue
                rec = run_cell(arch, shape, mp,
                               save_hlo=not args.no_hlo,
                               sketched=not args.no_sketch,
                               variant=args.variant, out_dir=args.out)
                save(rec, args.out)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    mem = rec.get("memory", {})
                    tot = (mem.get("temp_size_in_bytes", 0) +
                           mem.get("argument_size_in_bytes", 0))
                    extra = (f" compile={rec['compile_s']}s "
                             f"mem/dev={tot/2**30:.2f}GiB "
                             f"coll={rec['hlo']['coll_bytes_total']/2**30:.2f}GiB")
                elif status == "error":
                    n_fail += 1
                    extra = " " + rec["error"][:160]
                print(f"[{status}] {arch} {shape} "
                      f"{'2x16x16' if mp else '16x16'}{extra}", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
