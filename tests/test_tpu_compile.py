"""Real-width compiles of the main path's Pallas kernels for a described
TPU v5e chip (no chip attached: the TPU compiler is installed and
compiles for a topology it is told about). Each test asserts that the
compiled program holds the kernel as a `tpu_custom_call` — what the
interpreter-mode tests on the CPU cannot show.

Widths are those of `chip_smoke.py`'s cut: stablelm-12b (d_model 5120,
d_ff 13824, 32 query / 8 KV heads of 160), T = 2048 tokens, k = 17;
the attention's kernel path also at granite-34b's (48 query heads / 1
KV head of 128, T = 8192). The attention dispatcher asks the default
backend, which is the CPU here, so those tests tell it the platform.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch, reduced
from repro.kernels.csvec_quant import csvec_quant
from repro.kernels.flash_attention import flash_attention
from repro.kernels.sketch_update import sketch_update
from repro.models import attention
from repro.models.attention import chunked_causal_attention
from repro.models.transformer import SketchSettings
from repro.telemetry import attention_paths
from repro.train.state import RunConfig, init_train_state
from repro.train.step import make_train_step

T, K = 2048, 17


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host. Described here, inside a
    fixture, so that importing this file loads no TPU library; the
    persistent compilation cache is off around these compiles because a
    TPU executable written to it cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _custom_calls(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


@pytest.mark.parametrize("d", [5120, 13824])
def test_sketch_update_compiles_for_v5e(one_chip, d):
    args = (_sds(one_chip, (T, d), jnp.bfloat16),
            *(_sds(one_chip, (d, K)) for _ in range(3)),
            *(_sds(one_chip, (T, K)) for _ in range(3)),
            _sds(one_chip, (K,)))
    n = _custom_calls(
        lambda *a: sketch_update(*a, beta=0.95, interpret=False), *args)
    assert n >= 1


def test_flash_attention_compiles_for_v5e(one_chip):
    q = _sds(one_chip, (1, 32, T, 160), jnp.bfloat16)
    kv = _sds(one_chip, (1, 8, T, 160), jnp.bfloat16)
    n = _custom_calls(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True,
                                           interpret=False), q, kv, kv)
    assert n >= 1


def test_csvec_quant_compiles_for_v5e(one_chip):
    table = _sds(one_chip, (5, 1 << 16))
    n = _custom_calls(lambda t: csvec_quant(t, interpret=False), table)
    assert n >= 1


def _custom_call_op_names(hlo: str) -> list:
    """The `op_name` of each `tpu_custom_call` in compiled HLO text."""
    names = []
    for m in re.finditer(r'custom_call_target="tpu_custom_call"', hlo):
        rest = hlo[m.end():]
        end = re.search(r"\n  [%R]", rest)
        names.append(re.search(r'op_name="([^"]*)"',
                               rest[:end.start() if end else None]).group(1))
    return names


def _compiled_attention(monkeypatch, fn, *args) -> str:
    """Compiled HLO of `fn` with the attention dispatcher told it runs
    on a TPU; asserts the one trace took the kernel path."""
    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    before = attention_paths()
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    after = attention_paths()
    assert (after["kernel"] - before["kernel"],
            after["scan"] - before["scan"]) == (1, 0)
    return hlo


@pytest.mark.parametrize("hq,kv,seq,d", [(48, 1, 8192, 128),
                                         (32, 8, 2048, 160)],
                         ids=["granite-34b", "stablelm-12b"])
def test_causal_attention_kernel_compiles_for_v5e(one_chip, monkeypatch,
                                                  hq, kv, seq, d):
    """The vjp of `chunked_causal_attention` at a cell's widths: forward,
    dq and dkv kernels, no scan left, every kernel in the scope."""
    q = _sds(one_chip, (1, seq, kv, hq // kv, d), jnp.bfloat16)
    k = _sds(one_chip, (1, seq, kv, d), jnp.bfloat16)

    def fwd_bwd(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: chunked_causal_attention(
            *a, window=None), q, k, v)
        return out, vjp(do)

    hlo = _compiled_attention(monkeypatch, fwd_bwd, q, k, k, q)
    names = _custom_call_op_names(hlo)
    assert len(names) >= 3
    assert all("attention" in n for n in names), names
    whiles = re.findall(r"= [^\n]* while\([^\n]*op_name=\"([^\"]*)\"",
                        hlo)
    assert not [n for n in whiles if "attention" in n]


def test_train_step_attention_kernels_carry_the_scope(one_chip,
                                                      monkeypatch):
    """Lowered as the loop lowers it, with the cells' remat policy: the
    forward, its recompute, dq and dkv are kernels, and each names the
    `attention` scope as a component of its op_name, where the
    benchmark's in-step reading looks for it."""
    cfg = dataclasses.replace(reduced(get_arch("tinyllama-1.1b")),
                              remat_policy="dots_no_batch")
    run = RunConfig(global_batch=1, seq_len=256, total_steps=4,
                    warmup_steps=1,
                    sketch=SketchSettings(enabled=True, k_max=9))
    state = jax.tree.map(
        lambda t: _sds(one_chip, t.shape, t.dtype),
        jax.eval_shape(lambda key: init_train_state(key, cfg, run),
                       jax.random.PRNGKey(0)))
    batch = {n: _sds(one_chip, (1, 256), jnp.int32)
             for n in ("tokens", "labels")}
    hlo = _compiled_attention(monkeypatch, make_train_step(cfg, run),
                              state, batch)
    names = _custom_call_op_names(hlo)
    assert len(names) == 4, names
    assert all("attention" in n.split("/") for n in names), names
