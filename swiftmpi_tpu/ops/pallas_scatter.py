"""Pallas TPU experiment: scatter-add with the accumulator resident in VMEM.

The push half of the parity-mode word2vec step is a scatter-add of
~475K duplicated gradient rows into a capacity-sized accumulator
(transfer/xla.py ``_push_dense``).  On-chip round-2 measurements showed
XLA's scatter is even more transaction-bound than its gather (33ms
standalone at the bench shape, though far better when fused into the
step).  When the accumulator fits VMEM (demo.conf scale: 17K rows), the
whole reduction can run on-chip: stream index/grad blocks through the
grid and read-modify-write accumulator rows at VMEM latency.

Same contract as the gather experiment (ops/pallas_gather.py): the
kernel is correctness-tested in interpret mode on CPU; the on-chip A/B
lives in ``scripts/scatter_micro.py`` and records a calibration verdict
(ops/calibration.py) that gates wiring into the push path — absent a
measured win the XLA path is untouched.

Reference context: this replaces the server-side grad apply of
``MiniBatch::push`` (/root/reference/src/apps/word2vec/word2vec.h:314-317,
167-191), whose "accumulator" is the dense_hash_map row the handler
mutates in place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from swiftmpi_tpu.ops import calibration

_DEF_IDX_BLOCK = 4096


def _scatter_kernel(idx_ref, g_ref, out_ref):
    """One grid step: sequential RMW of one accumulator row per gradient
    row.  Duplicates within and across blocks are correct because the
    TPU grid and the fori_loop are both sequential.  The accumulator
    block revisits every step (constant index_map), so it stays resident
    and carries partial sums across the grid."""

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def body(j, _):
        # index read as an SMEM scalar: extracting it from a vector
        # value lowers to dynamic_slice, which Mosaic does not implement
        row = idx_ref[j]
        g = g_ref[pl.ds(j, 1), :]
        out_ref[pl.ds(row, 1), :] = out_ref[pl.ds(row, 1), :] + g
        return 0

    jax.lax.fori_loop(0, idx_ref.shape[0], body, 0)


@functools.partial(jax.jit,
                   static_argnames=("capacity", "idx_block", "interpret"))
def vmem_scatter_add(idx: jax.Array, grads: jax.Array, capacity: int,
                     idx_block: int = _DEF_IDX_BLOCK,
                     interpret: bool | None = None) -> jax.Array:
    """``zeros((capacity+1, W)).at[idx].add(grads)`` with the accumulator
    VMEM-resident.  ``idx`` must be pre-clipped to ``[0, capacity]`` —
    row ``capacity`` is the dump row for padding/invalid entries (the
    caller slices it off), mirroring the XLA path's ``mode="drop"``.
    ``idx`` length must be a multiple of ``idx_block``."""
    n = idx.shape[0]
    if n % idx_block:
        raise ValueError(f"idx length {n} not a multiple of {idx_block}")
    if interpret is None:
        interpret = not calibration.on_tpu()
    W = grads.shape[1]
    grid = (n // idx_block,)
    return pl.pallas_call(
        _scatter_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((idx_block,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((idx_block, W), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((capacity + 1, W), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((capacity + 1, W), grads.dtype),
        interpret=interpret,
    )(idx, grads)


def fits_vmem(capacity: int, width: int, itemsize: int = 4,
              idx_block: int = _DEF_IDX_BLOCK,
              budget_bytes: int = 12 << 20) -> bool:
    """Accumulator (+1 dump row, lane-padded width) + one idx/grad block
    under the conservative VMEM budget."""
    lanes = ((width + 127) // 128) * 128
    acc = (capacity + 1) * lanes * itemsize
    blk = idx_block * (4 + lanes * itemsize)
    return acc + blk <= budget_bytes


def use_vmem_scatter(capacity: int, width: int) -> bool:
    """Measurement-driven gate, same contract as
    ``pallas_gather.use_vmem_gather`` (shared policy in
    ``calibration.gated``): env ``SMTPU_PALLAS_SCATTER`` force-on/off;
    auto = single TPU device + fits VMEM + recorded chip win."""
    return calibration.gated("vmem_scatter", "SMTPU_PALLAS_SCATTER",
                             fits_vmem(capacity, width))


def masked_vmem_scatter_add(slots: jax.Array, valid: jax.Array,
                            grads: jax.Array, capacity: int) -> jax.Array:
    """Drop-in for the push path's dense scatter: routes invalid AND
    out-of-range slots to the dump row (exactly XLA's ``mode="drop"`` —
    an OOB slot must not corrupt the last real row), pads to an
    index-block multiple (padding also dumped), and returns the
    ``(capacity, W)`` accumulator."""
    n = slots.shape[0]
    ok = valid & (slots >= 0) & (slots < capacity)
    safe = jnp.where(ok, slots, capacity)
    pad = (-n) % _DEF_IDX_BLOCK
    if pad:
        safe = jnp.concatenate(
            [safe, jnp.full((pad,), capacity, slots.dtype)])
        grads = jnp.concatenate(
            [grads, jnp.zeros((pad, grads.shape[1]), grads.dtype)])
    acc = vmem_scatter_add(safe, grads, capacity)
    return acc[:capacity]
