"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret mode on CPU; Mosaic on the TPU target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, mlstm_chunk, sketch_update
from repro.kernels.ref import (
    flash_attention_ref, mlstm_chunk_ref, sketch_update_ref,
)


@pytest.mark.parametrize("T,d,k", [(128, 128, 5), (256, 128, 9),
                                   (128, 256, 33), (512, 128, 17),
                                   # ragged shapes: padded internally
                                   (130, 96, 7), (300, 192, 9)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sketch_update_sweep(rng, T, d, k, dtype):
    ks = jax.random.split(rng, 8)
    a = jax.random.normal(ks[0], (T, d), dtype)
    x = jax.random.normal(ks[1], (d, k), jnp.float32)
    y = jax.random.normal(ks[2], (d, k), jnp.float32)
    z = jax.random.normal(ks[3], (d, k), jnp.float32)
    ups, omg, phi = (jax.random.normal(ks[i], (T, k), jnp.float32)
                     for i in (4, 5, 6))
    psi = jax.random.normal(ks[7], (k,), jnp.float32)
    got = sketch_update(a, x, y, z, ups, omg, phi, psi, beta=0.9,
                        t_blk=128, d_blk=128)
    want = sketch_update_ref(a, x, y, z, ups, omg, phi, psi, 0.9)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("B,Hq,Hkv,S,D,window", [
    (1, 2, 1, 64, 16, None),
    (2, 4, 2, 128, 32, None),
    (1, 4, 4, 128, 16, 32),
    (2, 8, 2, 64, 64, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(rng, B, Hq, Hkv, S, D, window, dtype):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, Hq, S, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    got = flash_attention(q, k, v, causal=True, window=window,
                          q_blk=32, kv_blk=32)
    want = flash_attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,S,Dk,Dv,W", [
    (1, 2, 64, 16, 32, 16),
    (2, 2, 128, 8, 16, 32),
    (1, 4, 64, 32, 32, 64),
])
def test_mlstm_chunk_sweep(rng, B, H, S, Dk, Dv, W):
    ks = jax.random.split(rng, 5)
    q = jax.random.normal(ks[0], (B, H, S, Dk))
    k = jax.random.normal(ks[1], (B, H, S, Dk))
    v = jax.random.normal(ks[2], (B, H, S, Dv))
    li = jax.random.normal(ks[3], (B, H, S)) * 0.5
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, H, S)) + 2.0)
    h_k, (C_k, n_k, m_k) = mlstm_chunk(q, k, v, li, lf, chunk=W)
    z = lambda *s: jnp.zeros(s)
    h_r, (C_r, n_r, m_r) = mlstm_chunk_ref(
        q, k, v, li, lf, z(B, H, Dk, Dv), z(B, H, Dk), z(B, H), W)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(C_k), np.asarray(C_r),
                               atol=1e-4, rtol=1e-4)


def test_import_touches_no_backend_and_rule_reads_platform():
    """Importing the library initialises no JAX backend (an entry point
    must be able to place the compile cache or call
    `jax.distributed.initialize` first); the one interpret rule reads
    the platform only when asked, and interprets off the TPU."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "from jax._src import xla_bridge as xb\n"
        "import repro.kernels, repro.train.loop, repro.serve\n"
        "import repro.launch.mesh, repro.launch.compile_cache\n"
        "assert not xb.backends_are_initialized()\n"
        "from repro.kernels.ops import interpret_mode\n"
        "assert interpret_mode() is True\n"
        "print('OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
