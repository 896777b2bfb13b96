"""Differential ring-oracle tier (ISSUE 9, DESIGN.md §14).

The Pallas remote-DMA ring all-reduce must be BITWISE identical to its
pure-jnp oracle `ring_allreduce_ref` on CPU interpret — for every wire
dtype and every W in the tier — and the f32 ring must be a drop-in
psum (bitwise: XLA's CPU psum is the same sequential 0..W-1 left-fold
the pipelined-chain schedule implements).  The int8 ring additionally
satisfies the mass-conservation ledger: dequantized result + the
per-device folded residuals telescope to the f32 psum at ulp scale.

Both sides of every bitwise comparison run under jit — XLA CPU
contracts the residual subtract `s - q*sc` into an LLVM-level FMA that
`optimization_barrier` cannot pin, so an eager ref may differ from the
jitted kernel at cancellation-ulp scale (module docstring of
kernels/ring_allreduce.py).

Kernel-vs-ref cases run in subprocesses with their own fake-device
XLA_FLAGS (the main pytest process must keep seeing 1 device); the
hypothesis mass-conservation properties run host-side on the oracle
alone, which is the arithmetic contract the kernel is bitwise-locked
to by the other cases.
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.ring_differential

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


RING_CODE = """
    import jax, jax.numpy as jnp, numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.kernels.ring_allreduce import (
        ring_allreduce, ring_allreduce_ref)

    W = {w}
    mesh = Mesh(np.array(jax.devices()[:W]), ("data",))
    rng = np.random.default_rng({seed})
    # ragged lengths: sub-chunk, non-multiples of the 128 lane and of W,
    # and a multi-chunk size; wide dynamic range to stress the scales
    for N in (3, 129, 1000):
        xs = jnp.asarray(
            rng.standard_normal((W, N)) *
            (10.0 ** rng.integers(-3, 4, size=(W, 1))), jnp.float32)
        for wd in ("fp32", "int8"):
            def body(x, wd=wd):
                y, res = ring_allreduce(x[0], "data", axis_size=W,
                                        wire_dtype=wd)
                return y[None], res[None]
            f = shard_map(body, mesh=mesh, in_specs=P("data", None),
                          out_specs=(P("data", None), P("data", None)),
                          check_vma=False)
            y, res = jax.jit(f)(xs)
            y, res = np.asarray(y), np.asarray(res)
            for w in range(1, W):
                assert np.array_equal(y[0], y[w]), \\
                    (wd, N, "replicas differ")
            yr, resr = jax.jit(
                lambda xs, wd=wd: ring_allreduce_ref(xs, wire_dtype=wd)
            )(xs)
            yr, resr = np.asarray(yr), np.asarray(resr)
            assert np.array_equal(y[0], yr), (wd, N, "y not bitwise")
            assert np.array_equal(res, resr), (wd, N, "res not bitwise")
            if wd == "fp32":
                assert not res.any(), (N, "f32 residuals nonzero")
                ps = shard_map(lambda a: jax.lax.psum(a, "data"),
                               mesh=mesh, in_specs=P("data", None),
                               out_specs=P("data", None),
                               check_vma=False)
                yp = np.asarray(jax.jit(ps)(xs))[0]
                assert np.array_equal(yr, yp), \\
                    (N, "f32 ring is not bitwise psum")
            print("case OK", wd, N)
    print("OK")
"""


@pytest.mark.parametrize("w", [2, 4])
def test_ring_kernel_bitwise_vs_ref_and_psum(w):
    """Per-PR subset: W∈{2,4}, both wire dtypes, ragged N — kernel
    bitwise vs the jnp oracle (merged vector AND residual ledger,
    replicas identical), and the f32 oracle bitwise vs psum."""
    out = _run(RING_CODE.format(w=w, seed=w), devices=w)
    assert "OK" in out


@pytest.mark.slow
def test_ring_kernel_bitwise_vs_ref_and_psum_w8():
    """Nightly full width: same contract at W=8 (24 ragged-chunk
    pipeline hops)."""
    out = _run(RING_CODE.format(w=8, seed=8), devices=8)
    assert "OK" in out


def test_ring_w1_degenerate():
    """W=1 short-circuits: identity merge, zero residuals, no kernel."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ring_allreduce import ring_allreduce, \
        ring_allreduce_ref

    x = jnp.asarray(np.arange(7.0), jnp.float32)
    for wd in ("fp32", "int8"):
        y, res = ring_allreduce(x, "data", axis_size=1, wire_dtype=wd)
        assert np.array_equal(np.asarray(y), np.asarray(x))
        assert not np.asarray(res).any()
        yr, resr = ring_allreduce_ref(x[None], wire_dtype=wd)
        assert np.array_equal(np.asarray(yr), np.asarray(x))
        assert not np.asarray(resr).any()


def test_ring_rejects_unknown_wire_dtype():
    import jax.numpy as jnp
    import pytest as _pytest

    from repro.kernels.ring_allreduce import ring_allreduce, \
        ring_allreduce_ref

    x = jnp.zeros((4,), jnp.float32)
    with _pytest.raises(ValueError):
        ring_allreduce(x, "data", axis_size=2, wire_dtype="fp16")
    with _pytest.raises(ValueError):
        ring_allreduce_ref(x[None].repeat(2, 0), wire_dtype="fp16")


# ---------------------------------------------------------------------------
# hypothesis properties: int8 mass conservation + f32 psum exactness of
# the oracle arithmetic (host-side, derandomized `ci` profile in CI)
# ---------------------------------------------------------------------------


# guarded import so the kernel-vs-ref cases above still run where the
# dev-only hypothesis package is absent (same split as conftest.py)
try:
    from hypothesis import given, settings, strategies as st
    _HYP = True
except ImportError:
    _HYP = False
    needs_hypothesis = pytest.mark.skip(
        reason="property tests need the hypothesis package "
        "(pip install -r requirements-dev.txt)")

    def given(*_a, **_k):          # no-op decorators for collection:
        def deco(f):               # replace with an argless skip stub
            def stub():
                pass
            stub.__name__ = f.__name__
            stub.__doc__ = f.__doc__
            return needs_hypothesis(stub)
        return deco

    settings = given

if _HYP:
    @st.composite
    def _shards(draw):
        w = draw(st.sampled_from([2, 3, 4, 8]))
        n = draw(st.integers(min_value=1, max_value=600))
        seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
        expo = draw(st.integers(min_value=-3, max_value=4))
        import numpy as np

        rng = np.random.default_rng(seed)
        xs = (rng.standard_normal((w, n)) *
              (10.0 ** rng.integers(-2, 3, size=(w, 1))) *
              10.0 ** expo).astype(np.float32)
        return xs
else:
    def _shards():
        return None


@given(_shards())
@settings(max_examples=40, deadline=None)
def test_int8_ring_mass_conservation_property(xs):
    """dequant(result) + sum_d res_d == f32 psum, to ulp-scale bounds:
    each hop's identity s = dequant(q, sc) + res telescopes, so the
    only error left is the f32 rounding of the ledger itself."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ring_allreduce import ring_allreduce_ref

    y, res = jax.jit(
        lambda xs: ring_allreduce_ref(xs, wire_dtype="int8"))(
            jnp.asarray(xs))
    y64 = np.asarray(y, np.float64)
    res64 = np.asarray(res, np.float64)
    psum64 = xs.astype(np.float64).sum(axis=0)
    err = np.abs(y64 + res64.sum(axis=0) - psum64)
    # per-hop f32 rounding of the ledger entries: W hops, each bounded
    # by an ulp of the running magnitude
    scale = np.maximum(np.abs(xs).astype(np.float64).sum(axis=0), 1e-30)
    bound = 8.0 * xs.shape[0] * np.finfo(np.float32).eps * scale
    assert (err <= bound).all(), (err.max(), bound.min())


@given(_shards())
@settings(max_examples=25, deadline=None)
def test_f32_ring_oracle_is_exact_sequential_fold(xs):
    """The f32 oracle is the plain left-fold sum in worker order —
    bitwise equal to accumulating the shards sequentially in f32 (the
    arithmetic XLA's CPU psum performs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ring_allreduce import ring_allreduce_ref

    y, res = jax.jit(
        lambda xs: ring_allreduce_ref(xs, wire_dtype="fp32"))(
            jnp.asarray(xs))
    acc = xs[0].copy()
    for w in range(1, xs.shape[0]):
        acc = acc + xs[w]
    assert np.array_equal(np.asarray(y), acc)
    assert not np.asarray(res).any()
