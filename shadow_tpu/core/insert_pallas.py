"""Pallas TPU kernel for the insert mailbox gather.

The "sort2" select-sweep insert (core/events.py) needs each
destination row's arrivals as a contiguous [SWEEP, P] window of the
row-sorted candidate stream. Expressed as an XLA gather of H index
rows this lowers to an H-iteration serial HBM DMA loop (~1 us/row:
10.2 of 16.5 ms/window at 10,240-host PHOLD, measured r4 on v5e).
This kernel issues the SAME per-row copies as explicit async DMAs,
_DMA_DEPTH in flight, so their latencies overlap — the per-row copy
is the identical data movement, so values are bit-equal to the XLA
gather path by construction (tests/test_insert_impls.py drives the
gather form of the sweep on CPU; the kernel form is compared against
the gather op directly on device).

The stream stays in HBM (pl.BlockSpec memory_space ANY): staging it
in VMEM would pad the P-wide minor dim to the 128-lane tile, 12x the
real bytes (126 MB at 10k hosts — over the 128 MB VMEM). Only the
[B, SWEEP, 128] output block is VMEM-resident. The caller pads the
stream's minor dim to 128 because Mosaic requires DMA slices aligned
to the lane tile; the pad bytes ride otherwise-idle DMA bandwidth.
There is no stream-size ceiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK_HOSTS = 256
_DMA_DEPTH = 16


# The whole [H] start array rides in SMEM per grid step (in_specs[0]);
# SMEM is ~a few MB per core, so host counts far past the measured
# 102,400-host working point (400 KB of SMEM) would fail at compile
# time with no fallback — both lax.cond branches of the caller are
# always compiled. Gate conservatively: 2 MB of i32 starts.
_MAX_SMEM_START_ROWS = 512 * 1024


def mailbox_available(num_hosts: int) -> bool:
    """True when the Pallas TPU kernel can be used for `num_hosts`
    destination rows. The stream itself stays in HBM (no size
    ceiling); the gate is the [H] SMEM start table — callers past the
    bound take the XLA gather path instead of failing to compile.
    SHADOW_NO_PALLAS=1 disables the kernel (device-fault bisection;
    values are bit-identical either way)."""
    import os

    if os.environ.get("SHADOW_NO_PALLAS") == "1":
        return False
    return num_hosts <= _MAX_SMEM_START_ROWS


def _kernel(Wn: int, B: int, D: int, start_ref, stream_ref, out_ref,
            sem_ref):
    # One [Wn, P] HBM->VMEM DMA per destination row, D in flight —
    # the XLA gather runs the same copies strictly serially (~1 us
    # each, DMA latency bound); the pipeline overlaps them. i32 loop
    # state throughout: the package enables jax x64, and Mosaic
    # rejects i64 scalar loop carries (the caller traces this under
    # jax.enable_x64(False)).
    base = pl.program_id(0) * B

    def copy(k, slot):
        s = start_ref[base + k]
        return pltpu.make_async_copy(
            stream_ref.at[pl.ds(s, Wn), :], out_ref.at[k],
            sem_ref.at[slot])

    for d in range(D):  # static prologue: fill the pipeline
        copy(jnp.int32(d), jnp.int32(d)).start()

    def body(i, carry):
        slot = jax.lax.rem(i, jnp.int32(D))
        copy(i, slot).wait()

        @pl.when(i + D < B)
        def _():
            copy(i + jnp.int32(D), slot).start()

        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(B), body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("Wn",))
def mailbox_gather(stream, start, Wn: int):
    """[H, Wn, P] windows of `stream` ([n+pad, P] i32, row-sorted) at
    per-host offsets `start` ([H] i32, non-decreasing, start[h] <=
    n). Caller contract: the stream is padded by Wn rows at the end
    and to 128 lanes on the minor dim (Mosaic DMA alignment), and
    mailbox_available(H) was checked before building this path."""
    H = start.shape[0]
    P = stream.shape[1]
    B = next(b for b in (_BLOCK_HOSTS, 128, 64, 32, 16, 8, 4, 2, 1)
             if H % b == 0)
    D = min(_DMA_DEPTH, B)
    # The package runs with jax x64 on (int64 sim time), but every
    # array here is i32 and Mosaic rejects the i64 scalars x64-mode
    # tracing threads through the kernel's loop — trace the kernel
    # with x64 off.
    with jax.enable_x64(False):
        return _call(stream, start, Wn, H, P, B, D)


def _call(stream, start, Wn, H, P, B, D):
    return pl.pallas_call(
        functools.partial(_kernel, Wn, B, D),
        grid=(H // B,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (B, Wn, P), lambda b: (b, 0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((H, Wn, P), stream.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA((_DMA_DEPTH,))],
    )(start, stream)
