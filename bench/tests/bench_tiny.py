"""A cell of the benchmark cut to a size the CPU runs in seconds: the
same files, code and limits, with the widths, depth, vocabulary and
sequence made small and the compute in float32."""
import copy
import dataclasses

from bench import spec


def tiny_cell(name: str, seq: int = 64):
    c = spec.cell(name)
    cfg, t = copy.deepcopy(c.config), copy.deepcopy(c.traffic)
    m = cfg["model"]
    m.update(d_model=64, num_heads=4,
             num_kv_heads=min(m["num_kv_heads"], 2), head_dim=16,
             d_ff=128, num_layers=2, vocab_size=256, dtype="float32")
    for field, key in cfg["model_keys"].items():
        cfg["config"][key] = m[field]
    t.update(seq_len=seq)
    return dataclasses.replace(c, config=cfg, traffic=t)


CACHE_KEYS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
              "jax_persistent_cache_min_compile_time_secs")


def run_tiny(name: str, seed: int = 2 ** 31 + 11, seconds: float = 0.5):
    """One run of the tiny cell on the CPU, with JAX's persistent
    compilation cache off and its settings restored afterwards."""
    import jax

    from bench.run import run_cell

    saved = {k: getattr(jax.config, k) for k in CACHE_KEYS}
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return run_cell(tiny_cell(name), seed=seed, seconds=seconds,
                        trace=False, platform="cpu")
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
