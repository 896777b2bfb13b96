"""Quantization-aware ring all-reduce as a Pallas remote-DMA kernel.

DESIGN.md §14.  Replaces the *simulated* int8 wire (fake-quantize +
psum of dequantized grids) with a real ring: each hop moves int8
payload + one f32 scale per chunk over ``make_async_remote_copy``,
dequant-accumulates, requantizes, and folds the requantization residual
into a device-local ledger that the caller feeds back into the
per-worker error-feedback state (PR 4 mass-catch-up rule).

Schedule — pipelined chain, NOT the classic rotated ring
---------------------------------------------------------
XLA's CPU psum is a fixed sequential left-fold over workers ``0..W-1``
(verified bitwise at W=2/4/8).  The textbook ring folds chunk ``c``
starting at device ``c+1``, so its per-chunk fold order is a rotation —
bitwise-different from psum at W>=3 under floating point.  To keep the
"f32 ring == psum, bitwise" contract we run a pipelined chain instead:

  reduce:  chunk ``c`` folds in device order 0..W-1.  Device 0 initiates
           chunk ``t`` at hop ``t`` (stages into the send slot); device
           ``d>=1`` receives chunk ``c = t-(d-1)`` at hop ``t``, adds its
           own shard, and forwards.  All sends go ``d -> (d+1) % W``.
  bcast:   device W-1 holds the finals; it sends chunk ``c`` at hop
           ``W-1+c``; device ``d <= W-3`` forwards it at hop ``W+d+c``;
           device W-2 terminates the chain.  Total hops ``T = 3W-3``.

Every device sends every hop (dummy payload on inactive hops) so the
DMA semaphore pattern is uniform.  The price of psum fold order is
bandwidth: ~2N bytes through each device versus the classic ring's
``2N(W-1)/W`` — acceptable here because the payload is the already-tiny
sketch wire, and the int8 variant quarters the bytes again.

int8 hop arithmetic (requant points)
------------------------------------
Device 0 quantizes its shard per chunk (symmetric scalar scale
``amax/127``, round-half-even, clip to ±127); every reduce hop computes
``s = dequant(m, msc) + x[c]``, requantizes, and stores
``res[c] = s - dequant(q, sc)`` in the device-local residual output.
The broadcast phase forwards the *raw* (int8, scale) pair so all
replicas dequantize identical bits.  Telescoping the per-hop identities
gives the mass-conservation ledger

    dequant(result) + sum_d res_d  ==  f32 psum   (to ulp-scale error)

which tests/test_ring.py checks as a hypothesis property.

Verification contract (DESIGN.md §5 caveat applies)
---------------------------------------------------
``ring_allreduce_ref`` is a pure-jnp oracle running the identical
arithmetic sequence (explicit ``lax.fori_loop`` over devices).  The
kernel must match it BITWISE on CPU interpret — but only when both
sides are jitted: XLA CPU contracts ``s - q*sc`` into an LLVM-level FMA
that ``optimization_barrier`` cannot pin (it sits below HLO), so an
*eager* ref can differ from the jitted kernel at cancellation-ulp scale
in the residuals.  tests/test_ring.py jits both sides.  On real Mosaic
the contract weakens to allclose, same as every kernel in this repo.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

QMAX = 127.0  # symmetric int8 range, matches countsketch/csvec.py
_B = jax.lax.optimization_barrier
_LANE = 128


def _quant(s: Array) -> tuple[Array, Array]:
    """Per-chunk symmetric scalar quantization (barrier-pinned so the
    kernel and the jnp ref evaluate one canonical expression order)."""
    amax = _B(jnp.max(jnp.abs(s), axis=-1, keepdims=True))
    scale = _B(amax / QMAX)
    safe = _B(jnp.where(scale > 0, scale, 1.0))
    q = _B(jnp.clip(jnp.round(s / safe), -QMAX, QMAX).astype(jnp.int8))
    return q, scale


def _dequant(q: Array, scale: Array) -> Array:
    return _B(q.astype(jnp.float32) * scale)


def _kernel_f32(x_ref, y_ref, res_ref, send_ref, recv_ref,
                send_sem, recv_sem, *, axis_name, axis_size):
    W = axis_size
    d = jax.lax.axis_index(axis_name)
    dst = jax.lax.rem(d + 1, W)
    res_ref[...] = jnp.zeros_like(res_ref)
    for t in range(3 * W - 3):
        p = t % 2
        p1 = (t + 1) % 2
        if t < W:
            @pl.when(d == 0)
            def _():
                send_ref[p, :] = x_ref[t, :]
        rdma = pltpu.make_async_remote_copy(
            src_ref=send_ref.at[p], dst_ref=recv_ref.at[p],
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=dst, device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma.start()
        rdma.wait()
        m = recv_ref[p, :]
        # reduce processing: d >= 1 receives chunk c = t - (d-1)
        c_red = t - (d - 1)
        red_ok = (d >= 1) & (c_red >= 0) & (c_red < W)

        @pl.when(red_ok)
        def _():
            c = jnp.clip(c_red, 0, W - 1)
            s = _B(m + x_ref[c, :])

            @pl.when(d == W - 1)
            def _():
                y_ref[c, :] = s
            if t + 1 < 3 * W - 3:
                @pl.when(d <= W - 2)
                def _():
                    send_ref[p1, :] = s
                @pl.when(d == W - 1)
                def _():
                    send_ref[p1, :] = s
        # broadcast processing: d < W-1 receives chunk c = t - (W-1) - d
        c_bc = t - (W - 1) - d
        bc_ok = (d < W - 1) & (c_bc >= 0) & (c_bc < W)

        @pl.when(bc_ok)
        def _():
            c = jnp.clip(c_bc, 0, W - 1)
            y_ref[c, :] = m
            if t + 1 < 3 * W - 3:
                @pl.when(d <= W - 3)
                def _():
                    send_ref[p1, :] = m


def _kernel_int8(x_ref, y_ref, res_ref, send_ref, recv_ref,
                 sscale_ref, rscale_ref, send_sem, recv_sem,
                 ssc_sem, rsc_sem, *, axis_name, axis_size):
    W = axis_size
    d = jax.lax.axis_index(axis_name)
    dst = jax.lax.rem(d + 1, W)
    res_ref[...] = jnp.zeros_like(res_ref)
    for t in range(3 * W - 3):
        p = t % 2
        p1 = (t + 1) % 2
        if t < W:
            @pl.when(d == 0)
            def _():
                s = x_ref[t, :]
                q, sc = _quant(s)
                send_ref[p, :] = q
                sscale_ref[p, :] = sc
                res_ref[t, :] = _B(s - _dequant(q, sc))
        rdma = pltpu.make_async_remote_copy(
            src_ref=send_ref.at[p], dst_ref=recv_ref.at[p],
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=dst, device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma2 = pltpu.make_async_remote_copy(
            src_ref=sscale_ref.at[p], dst_ref=rscale_ref.at[p],
            send_sem=ssc_sem, recv_sem=rsc_sem,
            device_id=dst, device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma.start()
        rdma2.start()
        rdma.wait()
        rdma2.wait()
        m = recv_ref[p, :]
        msc = rscale_ref[p, :]
        c_red = t - (d - 1)
        red_ok = (d >= 1) & (c_red >= 0) & (c_red < W)

        @pl.when(red_ok)
        def _():
            c = jnp.clip(c_red, 0, W - 1)
            s = _B(_dequant(m, msc) + x_ref[c, :])
            q, sc = _quant(s)
            res_ref[c, :] = _B(s - _dequant(q, sc))

            @pl.when(d == W - 1)
            def _():
                y_ref[c, :] = _dequant(q, sc)
            if t + 1 < 3 * W - 3:
                @pl.when(d <= W - 1)
                def _():
                    send_ref[p1, :] = q
                    sscale_ref[p1, :] = sc
        c_bc = t - (W - 1) - d
        bc_ok = (d < W - 1) & (c_bc >= 0) & (c_bc < W)

        @pl.when(bc_ok)
        def _():
            c = jnp.clip(c_bc, 0, W - 1)
            y_ref[c, :] = _dequant(m, msc)
            if t + 1 < 3 * W - 3:
                @pl.when(d <= W - 3)
                def _():
                    send_ref[p1, :] = m
                    sscale_ref[p1, :] = msc


def _chunk_len(n: int, workers: int) -> int:
    s = -(-n // workers)
    return -(-s // _LANE) * _LANE


def ring_allreduce(
    x: Array,
    axis_name: str,
    *,
    axis_size: int,
    wire_dtype: str = "fp32",
    interpret: bool | None = None,
) -> tuple[Array, Array]:
    """All-reduce a flat f32 vector over ``axis_name`` via the ring.

    Must be called INSIDE a shard_map over ``axis_name`` with
    ``axis_size`` devices.  Returns ``(y, res)``: the merged vector
    (replicated — bitwise identical on every device) and this device's
    quantization-residual vector (zeros for fp32 wire).
    """
    if wire_dtype not in ("fp32", "int8"):
        raise ValueError(f"unsupported wire_dtype {wire_dtype!r}")
    W = axis_size
    x = x.astype(jnp.float32)
    if W == 1:
        return x, jnp.zeros_like(x)
    if interpret is None:
        from repro.kernels.ops import interpret_mode
        interpret = interpret_mode()
    (N,) = x.shape
    S = _chunk_len(N, W)
    xp = jnp.zeros((W * S,), jnp.float32).at[:N].set(x).reshape(W, S)
    out_shape = (jax.ShapeDtypeStruct((W, S), jnp.float32),
                 jax.ShapeDtypeStruct((W, S), jnp.float32))
    if wire_dtype == "int8":
        kern = functools.partial(_kernel_int8, axis_name=axis_name,
                                 axis_size=W)
        scratch = [
            pltpu.VMEM((2, S), jnp.int8), pltpu.VMEM((2, S), jnp.int8),
            pltpu.VMEM((2, 1), jnp.float32),
            pltpu.VMEM((2, 1), jnp.float32),
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
        ]
    else:
        kern = functools.partial(_kernel_f32, axis_name=axis_name,
                                 axis_size=W)
        scratch = [
            pltpu.VMEM((2, S), jnp.float32),
            pltpu.VMEM((2, S), jnp.float32),
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
        ]
    kwargs = {}
    if not interpret:
        # real Mosaic needs the collective_id for the cross-device sems
        kwargs["compiler_params"] = pltpu.CompilerParams(
            collective_id=0)
    y, res = pl.pallas_call(
        kern, out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret, **kwargs)(xp)
    return y.reshape(-1)[:N], res.reshape(-1)[:N]


def ring_allreduce_ref(xs: Array, *, wire_dtype: str = "fp32"
                       ) -> tuple[Array, Array]:
    """Pure-jnp differential oracle: the identical arithmetic sequence
    as the kernel, run on the stacked ``(W, N)`` per-device shards.

    Returns ``(y, res)`` with ``y`` the merged flat vector and ``res``
    the ``(W, N)`` per-device residual ledger.  Jit this when comparing
    against the kernel (see module docstring — the bitwise contract
    holds under jit on both sides).
    """
    W, N = xs.shape
    xs = xs.astype(jnp.float32)
    if W == 1:
        return xs[0], jnp.zeros_like(xs)
    S = _chunk_len(N, W)
    xp = jnp.zeros((W, W * S), jnp.float32).at[:, :N].set(xs)
    xp = xp.reshape(W, W, S)  # [device, chunk, lane]
    if wire_dtype == "fp32":
        def body(dd, acc):
            return _B(acc + jax.lax.dynamic_index_in_dim(
                xp, dd, keepdims=False))
        y = jax.lax.fori_loop(1, W, body, xp[0])
        res = jnp.zeros((W, W, S), jnp.float32)
    elif wire_dtype == "int8":
        q0, sc0 = _quant(xp[0])
        res = jnp.zeros((W, W, S), jnp.float32)
        res = res.at[0].set(_B(xp[0] - _dequant(q0, sc0)))

        def body(dd, carry):
            q, sc, r = carry
            s = _B(_dequant(q, sc) + jax.lax.dynamic_index_in_dim(
                xp, dd, keepdims=False))
            q2, sc2 = _quant(s)
            r = r.at[dd].set(_B(s - _dequant(q2, sc2)))
            return q2, sc2, r
        q, sc, res = jax.lax.fori_loop(1, W, body, (q0, sc0, res))
        y = _dequant(q, sc)
    else:
        raise ValueError(f"unsupported wire_dtype {wire_dtype!r}")
    return y.reshape(-1)[:N], res.reshape(W, -1)[:, :N]


def ring_wire_bytes(n: int, workers: int, wire_dtype: str) -> int:
    """Per-device bytes moved through the ring for an n-element vector:
    (3W-3) hops x one chunk each (payload + scale on the int8 wire)."""
    if workers <= 1:
        return 0
    s = _chunk_len(n, workers)
    hops = 3 * workers - 3
    if wire_dtype == "int8":
        return hops * (s + 4)
    return hops * s * 4
