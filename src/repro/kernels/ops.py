"""Jit'd public wrappers for the Pallas kernels.

`use_pallas(True)` routes model hot spots through the TPU kernels; the
default (False) keeps XLA-native implementations. Whether a kernel
runs compiled (Mosaic) or in the Pallas interpreter follows one rule,
`interpret_mode()`: every kernel whose ``interpret`` argument is left
as None asks it when it is traced, so importing this package touches
no backend and no kernel runs interpreted on a TPU.
"""
from __future__ import annotations

import jax

from repro.kernels.csvec_insert import csvec_insert
from repro.kernels.csvec_quant import csvec_quant
from repro.kernels.csvec_topk import csvec_topk
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_chunk import mlstm_chunk
from repro.kernels.psparse_update import psparse_update
from repro.kernels.ring_allreduce import ring_allreduce, ring_allreduce_ref
from repro.kernels.sketch_update import sketch_update

_FLAGS = {"use_pallas": False}


def use_pallas(enable: bool = True) -> None:
    _FLAGS["use_pallas"] = enable


def pallas_enabled() -> bool:
    return _FLAGS["use_pallas"]


def interpret_mode() -> bool:
    """Interpret off the TPU (CPU validation), compiled Mosaic on it.
    Reads the default backend when called, never at import."""
    return jax.default_backend() != "tpu"


__all__ = [
    "sketch_update", "psparse_update", "flash_attention", "mlstm_chunk",
    "csvec_insert", "csvec_quant", "csvec_topk", "ring_allreduce",
    "ring_allreduce_ref", "use_pallas", "pallas_enabled",
    "interpret_mode",
]
