"""Real-width compiles of the main path's Pallas kernels for a described
TPU v5e chip (no chip attached: the TPU compiler is installed and
compiles for a topology it is told about). Each test asserts that the
compiled program holds the kernel as a `tpu_custom_call` — what the
interpreter-mode tests on the CPU cannot show.

Widths are those of `chip_smoke.py`'s cut: stablelm-12b (d_model 5120,
d_ff 13824, 32 query / 8 KV heads of 160), T = 2048 tokens, k = 17.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.csvec_quant import csvec_quant
from repro.kernels.flash_attention import flash_attention
from repro.kernels.sketch_update import sketch_update

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
