"""Pallas TPU kernel: DMA ring exchange for the sparse push buckets.

``TpuTransfer._build_push`` ships each device's per-home-shard request
ids and (grads | count) buckets with two dense ``jax.lax.all_to_all``
calls.  On an ICI ring that is a synchronous, XLA-scheduled exchange;
SNIPPETS.md [1] and Near-Optimal Sparse Allreduce (PAPERS.md) show the
alternative: stream each bucket to its home shard directly with
``pltpu.make_async_remote_copy`` steps so the NIC-side DMA engines
overlap all n-1 transfers instead of round-tripping through one fused
collective.

``ring_exchange(x, axis, n)`` is a drop-in for
``jax.lax.all_to_all(x, axis, 0, 0, tiled=True)`` on a (n, C, ...)
operand inside ``shard_map``: block j of the result is the block this
device received from device j.  Schedule: the local block is copied by
an on-chip DMA; then, at ring step s = 1..n-1, this device RDMA-sends
block ``(my_id + s) % n`` of its operand into slot ``my_id`` of the
receiver's output — every device sends to distance-s neighbor at step
s, so each step is a pure ring shift and the n-1 steps saturate both
ICI directions.  All sends start before any wait (the per-step DMA
semaphore pairs keep completion accounting exact).

Device addressing uses scalar ``DeviceIdType.LOGICAL`` ids — the mesh
must be 1-D over ``axis`` (``use_ring_push`` refuses otherwise), which
keeps the logical id equal to the axis index on chip and is the only
form the interpret-mode discharge rule supports, so the 8-device CPU
parity tests exercise the same DMA schedule.

Routing: ``use_ring_push`` resolves the ``[cluster] data_plane:`` knob
via ``calibration.data_plane_gated`` (kernel name ``ring_push``, env
``SMTPU_RING_PUSH``) — absent a measured on-chip win on a real
multi-chip mesh, the ``all_to_all`` wire exchange stays.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from swiftmpi_tpu.ops import calibration

_LANES = 128
#: elements in one (32, 128) tile — whole tiles for every dtype down to
#: 8 bits
_TILE_ELEMS = 32 * _LANES


def _ring_kernel(n: int, barrier: bool, my_id_ref, x_ref, out_ref,
                 local_sem, send_sem, recv_sem):
    my_id = my_id_ref[0]
    if barrier:
        # on chip every peer must have entered the kernel before
        # anything is written into its output buffer (the interpreter
        # runs the devices in lockstep and has no barrier semaphore)
        sem = pltpu.get_barrier_semaphore()
        for s in range(1, n):
            pltpu.semaphore_signal(
                sem, inc=1, device_id=jax.lax.rem(my_id + s, n),
                device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(sem, n - 1)
    # local block: an on-chip DMA, no wire (x/out live in HBM, which
    # Mosaic cannot load from or store to directly)
    local = pltpu.make_async_copy(x_ref.at[pl.ds(my_id, 1)],
                                  out_ref.at[pl.ds(my_id, 1)], local_sem)
    local.start()

    def step(s):
        dst = jax.lax.rem(my_id + s, n)
        return pltpu.make_async_remote_copy(
            src_ref=x_ref.at[pl.ds(dst, 1)],
            dst_ref=out_ref.at[pl.ds(my_id, 1)],
            send_sem=send_sem.at[s - 1],
            recv_sem=recv_sem.at[s - 1],
            device_id=dst,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )

    # start all n-1 sends, then wait all: the DMA engines overlap the
    # transfers; per-step semaphores keep each send/recv pair exact
    for s in range(1, n):
        step(s).start()
    local.wait()
    for s in range(1, n):
        step(s).wait()


@functools.partial(jax.jit, static_argnames=("axis", "n", "interpret"))
def ring_exchange(x: jax.Array, axis: str, n: int,
                  interpret: bool | None = None) -> jax.Array:
    """``jax.lax.all_to_all(x, axis, 0, 0, tiled=True)`` by DMA ring.

    ``x`` is this device's (n, C, ...) operand under ``shard_map``
    (first axis indexed by destination device); the result's block j is
    the block received from device j.  ``n`` must equal the size of
    ``axis`` and the mesh must be 1-D (see module docstring)."""
    if x.shape[0] != n:
        raise ValueError(
            f"ring_exchange: leading dim {x.shape[0]} != axis size {n}")
    if interpret is None:
        interpret = not calibration.on_tpu()
    my_id = jax.lax.axis_index(axis).reshape((1,)).astype(jnp.int32)
    # Mosaic can only slice a block off an UNTILED leading dim of whole
    # (sublane, lane) tiles: a (n, C) int32 bucket tiles its first dim,
    # a (n, C, 101) grad bucket has a ragged lane dim.  Exchange the
    # bucket as (n, R, 128) with each block zero-padded to whole tiles.
    shape, block_len = x.shape, math.prod(x.shape[1:])
    flat = x.reshape(n, block_len)
    pad = (-block_len) % _TILE_ELEMS
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    x = flat.reshape(n, -1, _LANES)
    out = pl.pallas_call(
        functools.partial(_ring_kernel, n, not interpret),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                        pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
                        pltpu.SemaphoreType.DMA((max(n - 1, 1),))],
        compiler_params=pltpu.CompilerParams(collective_id=0),
        interpret=interpret,
    )(my_id, x)
    return out.reshape(n, -1)[:, :block_len].reshape(shape)


def use_ring_push(n: int, single_axis: bool, mode: str = "auto") -> bool:
    """Should the push wire exchange route through the DMA ring?
    Requires a real exchange (n > 1) and a 1-D mesh over the shard
    axis (``single_axis`` — LOGICAL device ids equal axis indices only
    there; the hybrid data x shard mesh keeps ``all_to_all``).  Above
    that, the ``[cluster] data_plane:`` knob / ``SMTPU_RING_PUSH`` env
    resolution is the shared measured-verdict policy (``manual=True``:
    the caller is inside ``shard_map``, operands are device-local)."""
    fits = n > 1 and single_axis
    return calibration.data_plane_gated(
        mode, "ring_push", "SMTPU_RING_PUSH", fits, manual=True)
