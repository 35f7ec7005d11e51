"""The sparse push's read-modify-write by whole 8-row tiles: one Pallas
kernel a push (``transfer/xla.py``'s ``tiles`` form).

A row of an f32 field of 128-multiple width cannot be sliced out of HBM
alone (Mosaic: a slice along dimension 0 must be aligned to the tiling,
8), but the 8 rows of its tile can, and they are contiguous there
(``T(8,128)``, row-major): ``8 x width`` floats, 12 KB at 384 lanes.  The
kernel walks the push's distinct rows, ascending, so the rows of one tile
are neighbours: a tile is copied into a ring of VMEM slots, every named
row of it updated by the access method's own ``apply_push``, and copied
back, with the reads half a ring of slots in front of the updates and a
ring slot's write awaited only when the slot is taken again.  The
fields stay in HBM (``pl.ANY``), aliased in to out: nothing but the named
tiles moves.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

#: rows of a tile: the sublanes of an f32 vector register
TILE = 8
#: slots of the push a grid step holds: its rows in SMEM (whose 1-D
#: int32 arrays XLA lays out 1,024 at a time: a block is a multiple of
#: that, or the whole push), its gradients a VMEM block through the
#: ordinary pipeline.  A step drains its writes before it ends, so that a
#: tile two steps share is read by the second as the first left it.
BLOCK = 1024
#: ring slots a field: tiles in flight.  The reads run half as many slots
#: of the push in front of the updates, so a ring slot's write has had
#: that long when the slot is taken again.  v5e micro, PERF.md section 6,
#: PR 47: 16 / 32 / 64 / 128 slots cost 12.26 / 11.99 / 12.02 / 12.12 ms
#: for 146,000 rows of one field — the copies' issue rate bounds the
#: kernel, not their latency.
DEPTH = 32
#: what the kernel may ask of VMEM for its ring and its gradient blocks
_VMEM_LIMIT = 100 << 20


def _pallas():
    """Pallas and its TPU dialect, imported by the process whose push
    first comes here and by no other.  The import is ~1 s of compiling
    Python sources (Mosaic's dialects, the GPU back end beside them) where
    the installation keeps no byte code (``PYTHONDONTWRITEBYTECODE``), ~2 s
    on the benchmark's host and most of what the kernel costs a run's
    set-up: where a persistent compile cache is configured the byte code
    is kept in it too, beside the compiled programs, and read back by the
    next process as they are."""
    cache = jax.config.jax_compilation_cache_dir
    held = sys.dont_write_bytecode, sys.pycache_prefix
    if cache and sys.pycache_prefix is None:
        sys.dont_write_bytecode = False
        sys.pycache_prefix = os.path.join(cache, "pycache")
    try:
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
    finally:
        sys.dont_write_bytecode, sys.pycache_prefix = held
    return pl, pltpu


def rmw_tiles(fields: dict, rows: jax.Array, grads: dict, access,
              n: jax.Array, inv=None) -> dict:
    """``fields`` (name -> ``f32[capacity, width]``, width a multiple of
    128) with the rows ``rows[:n]`` read, `access.apply_push`-ed with
    ``grads`` (times ``inv``, ``(B, 1)``, where given) and written back.
    ``rows``: ascending and distinct up to ``n``, every one of them inside
    a tile that lies wholly inside the fields (below ``capacity -
    capacity % 8``); what stands behind ``n`` is never read."""
    pl, pltpu = _pallas()
    names = tuple(fields)
    families = tuple(grads)
    B = rows.shape[0]
    block = min(BLOCK, -(-B // TILE) * TILE)
    depth, ahead = DEPTH, DEPTH // 2
    vmem = 4 * (depth * TILE * sum(x.shape[1] for x in fields.values())
                + 2 * block * sum(x.shape[1] for x in grads.values()))
    # a read and a write semaphore a ring slot a field, of the ~500 a
    # kernel may hold; the widest touched fields in the tree: two of 768
    assert 2 * len(names) * depth <= 448 and 2 * vmem <= _VMEM_LIMIT, (
        len(names), vmem)
    n_blocks = -(-B // block)
    written = tuple(jax.eval_shape(
        access.apply_push,
        {f: jax.ShapeDtypeStruct((1, x.shape[1]), x.dtype)
         for f, x in fields.items()},
        {g: jax.ShapeDtypeStruct((1, x.shape[1]), x.dtype)
         for g, x in grads.items()}))
    rows = jnp.pad(rows, (0, n_blocks * block - B))

    def kernel(n_ref, rows_ref, *refs):
        refs = list(refs)
        inv_ref = refs.pop(0) if inv is not None else None
        grad_refs = dict(zip(families, refs[:len(families)]))
        del refs[:len(families) + len(names)]        # the fields, as given
        out = dict(zip(names, refs[:len(names)]))
        ring = dict(zip(names, refs[len(names):2 * len(names)]))
        read_sem, write_sem = refs[2 * len(names):]
        m = jnp.clip(n_ref[0] - pl.program_id(0) * block, 0, block)

        def tile_of(j):
            return rows_ref[j] >> 3

        def copies(slot, tile, which, to_ring):
            at = pl.ds(pl.multiple_of(tile * TILE, TILE), TILE)
            for k, f in enumerate(names):
                if f not in which:
                    continue
                hbm, vmem = out[f].at[at, :], ring[f].at[slot]
                yield (pltpu.make_async_copy(hbm, vmem, read_sem.at[k, slot])
                       if to_ring else
                       pltpu.make_async_copy(vmem, hbm, write_sem.at[k, slot]))

        def read_ahead(ja, started):
            """Start the read of slot ``ja``'s tile where ``ja`` opens a
            run; ``started``: the runs whose reads have been."""
            jc = jnp.minimum(ja, block - 1)
            opens = (ja < m) & ((ja == 0) | (
                tile_of(jc) != tile_of(jnp.maximum(jc - 1, 0))))

            @pl.when(opens)
            def _():
                slot = started % depth

                @pl.when(started >= depth)
                def _():
                    for c in copies(slot, 0, written, to_ring=False):
                        c.wait()
                for c in copies(slot, tile_of(jc), names, to_ring=True):
                    c.start()
            return started + opens.astype(jnp.int32)

        def update(j, carry):
            done, started = carry
            started = read_ahead(j + ahead, started)
            row = rows_ref[j]
            tile, sub = row >> 3, pl.ds(row & 7, 1)
            opens = (j == 0) | (tile != tile_of(jnp.maximum(j - 1, 0)))
            closes = (j == m - 1) | (
                tile != tile_of(jnp.minimum(j + 1, block - 1)))
            slot = done % depth

            @pl.when(opens)
            def _():
                for c in copies(slot, 0, names, to_ring=True):
                    c.wait()
            at = pl.ds(j, 1)
            g = {f: grad_refs[f][at, :] for f in families}
            if inv_ref is not None:
                g = {f: x * inv_ref[j] for f, x in g.items()}
            new = access.apply_push({f: ring[f][slot, sub, :] for f in names},
                                    g)
            for f in written:
                ring[f][slot, sub, :] = new[f]

            @pl.when(closes)
            def _():
                for c in copies(slot, tile, written, to_ring=False):
                    c.start()
            return done + closes.astype(jnp.int32), started

        @pl.when(m > 0)
        def _():
            started = jax.lax.fori_loop(0, ahead, read_ahead, jnp.int32(0))
            done, _ = jax.lax.fori_loop(0, m, update, (jnp.int32(0), started))

            def drain(slot, _):
                @pl.when(slot < done)
                def _():
                    for c in copies(slot, 0, written, to_ring=False):
                        c.wait()
                return 0
            jax.lax.fori_loop(0, depth, drain, 0)

    def head_block(i, n_ref):
        # a step behind the head asks for the head's last block again:
        # the pipeline copies nothing for it
        return jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0) // block)

    in_specs = [pl.BlockSpec((block,), lambda i, n_ref: (head_block(i, n_ref),),
                             memory_space=pltpu.SMEM)]
    operands = [rows]
    if inv is not None:
        in_specs.append(in_specs[0])
        operands.append(jnp.pad(inv[:, 0], (0, n_blocks * block - B)))
    for f in families:
        in_specs.append(pl.BlockSpec(
            (block, grads[f].shape[1]),
            lambda i, n_ref: (head_block(i, n_ref), 0)))
        operands.append(grads[f])
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(names)
    operands += [fields[f] for f in names]
    first_field = len(operands) - len(names) + 1     # after ``n``
    new = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_blocks,), in_specs=in_specs,
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(names),
            scratch_shapes=[
                *(pltpu.VMEM((depth, TILE, fields[f].shape[1]), jnp.float32)
                  for f in names),
                pltpu.SemaphoreType.DMA((len(names), depth)),
                pltpu.SemaphoreType.DMA((len(names), depth))]),
        out_shape=[jax.ShapeDtypeStruct(fields[f].shape, fields[f].dtype)
                   for f in names],
        input_output_aliases={first_field + k: k for k in range(len(names))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 2 * vmem)),
        name="rmw_tiles",
    )(jnp.reshape(n, (1,)).astype(jnp.int32), *operands)
    return dict(zip(names, new))
