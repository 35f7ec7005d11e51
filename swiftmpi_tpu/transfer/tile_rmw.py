"""The sparse push's read-modify-write by whole 8-row tiles: one Pallas
kernel a push (``transfer/xla.py``'s ``tiles`` form).

A row of an f32 field of 128-multiple width cannot be sliced out of HBM
alone (Mosaic: a slice along dimension 0 must be aligned to the tiling,
8), but the 8 rows of its tile can, and they are contiguous there
(``T(8,128)``, row-major): ``8 x width`` floats, 12 KB at 384 lanes.  So
are adjacent tiles: a run of named tiles in a row is one slice.  The
kernel walks the push's distinct rows, ascending, a grid step at a time:
it first cuts the step's rows into COPIES — a tile and the named tiles
that follow it in the field, `RUN` at most — and then takes the copies in
turn: a copy is read into a slot of a ring in VMEM (one DMA a field, as
long as the copy), every named row of it updated by the access method's
own ``apply_push``, and written back, with the reads half a ring of
copies in front of the updates and a ring slot's write awaited only when
the slot is taken again.  What the scalar core decides it decides once a
copy, not once a row.  The fields stay in HBM (``pl.ANY``), aliased in to
out: nothing but the named tiles moves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from swiftmpi_tpu.utils.xla_env import pallas as _pallas

#: rows of a tile: the sublanes of an f32 vector register
TILE = 8
#: slots of the push a grid step holds: its rows in SMEM (whose 1-D
#: int32 arrays XLA lays out 1,024 at a time: a block is a multiple of
#: that, or the whole push), its gradients a VMEM block through the
#: ordinary pipeline.  A step drains its writes before it ends, so that a
#: tile two steps share is read by the second as the first left it.
BLOCK = 1024
#: ring slots a field: copies in flight.  The reads run half as many
#: copies in front of the updates, so a ring slot's write has had that
#: long when the slot is taken again.  v5e micro, PERF.md section 6,
#: PR 48: 16 / 32 / 64 slots cost 9.62 / 9.27 / 9.45 ms for the 145,447
#: rows (98,716 tiles, 50,726 copies) of a cbow2m-b16k target push, two
#: fields.
DEPTH = 32
#: tiles a copy moves at most, and a ring slot holds: a run of adjacent
#: named tiles is cut into copies of 1..`RUN` tiles.  A copy costs the
#: same to start and await whatever its length (~83 ns for a parameter
#: and its accumulator both ways, beside ~35 ns a row) until the memory
#: binds (98,304 tiles in runs of 1 / 2 / 4 / 8: 11.61 / 8.29 / 8.29 /
#: 8.22 ms, 4.7 GB at ~570 GB/s).  The same micro, that push at 1 / 2 /
#: 4 / 8 / 16 tiles a copy: 13.08 / 10.28 / 9.36 / 9.27 / 9.26 ms
#: (98.7 / 66.9 / 54.3 / 50.7 / 49.9 K copies).
RUN = 8
#: what the kernel may ask of VMEM for its ring and its gradient blocks
_VMEM_LIMIT = 100 << 20


def rmw_tiles(fields: dict, rows: jax.Array, grads: dict, access,
              n: jax.Array, inv=None) -> tuple:
    """``fields`` (name -> ``f32[capacity, width]``, width a multiple of
    128) with the rows ``rows[:n]`` read, `access.apply_push`-ed with
    ``grads`` (times ``inv``, ``(B, 1)``, where given) and written back,
    and the copies that took one way a field (``int32``): the head's runs
    of adjacent tiles, cut every `RUN` tiles and where a grid step ends.
    ``rows``: ascending and distinct up to ``n``, every one of them inside
    a tile that lies wholly inside the fields (below ``capacity -
    capacity % 8``); what stands behind ``n`` is never read."""
    pl, pltpu = _pallas()
    names = tuple(fields)
    families = tuple(grads)
    B = rows.shape[0]
    block = min(BLOCK, -(-B // TILE) * TILE)
    depth, ahead = DEPTH, DEPTH // 2
    vmem = 4 * (depth * RUN * TILE * sum(x.shape[1] for x in fields.values())
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

    def kernel(n_ref, rows_ref, *refs):
        refs = list(refs)
        inv_ref = refs.pop(0) if inv is not None else None
        grad_refs = dict(zip(families, refs[:len(families)]))
        del refs[:len(families) + len(names)]        # the fields, as given
        out = dict(zip(names, refs[:len(names)]))
        copies_ref = refs[len(names)]
        ring = dict(zip(names, refs[len(names) + 1:2 * len(names) + 1]))
        # a copy's first slot of the step (behind the last: ``m``) and
        # its tiles
        read_sem, write_sem, first, tiles_of = refs[2 * len(names) + 1:]
        m = jnp.clip(n_ref[0] - pl.program_id(0) * block, 0, block)

        def cut(j, carry):
            """Slot ``j`` into the step's copies: it opens one where its
            tile is neither the tile in front nor, with room in that
            tile's copy, the next of the field.  No branch and no read
            but the row's: a slot that opens nothing writes where the
            next copy's opening, or the end, will."""
            count, tiles, before = carry
            tile = rows_ref[j] >> 3
            new = (j < m) & (tile != before)
            opens = new & ((tile != before + 1) | (tiles == RUN))
            first[count] = j
            count += opens.astype(jnp.int32)
            tiles = jnp.where(opens, 1, tiles + new.astype(jnp.int32))
            tiles_of[count - 1] = tiles
            return count, tiles, tile

        def copies(slot, row, rows, which, to_ring):
            """The DMAs of ``rows`` rows from ``row`` on between the
            fields ``which`` and ring slot ``slot``; one to await takes
            the copy's length alone."""
            at, rows = pl.multiple_of(row, TILE), pl.multiple_of(rows, TILE)
            for k, f in enumerate(names):
                if f not in which:
                    continue
                hbm = out[f].at[pl.ds(at, rows), :]
                vmem = ring[f].at[slot, pl.ds(0, rows)]
                yield (pltpu.make_async_copy(hbm, vmem, read_sem.at[k, slot])
                       if to_ring else
                       pltpu.make_async_copy(vmem, hbm, write_sem.at[k, slot]))

        def row_of(c):
            """The first row of copy ``c``'s first tile (behind the last
            copy: of the step's last row's)."""
            return (rows_ref[jnp.minimum(first[c], m - 1)] >> 3) * TILE

        def read(c, reused):
            """Start copy ``c``'s read; where its ring slot has been
            taken before (``reused``), that copy's write done first."""
            slot = c % depth
            if reused:
                for copy in copies(slot, 0, tiles_of[c - depth] * TILE,
                                   written, to_ring=False):
                    copy.wait()
            for copy in copies(slot, row_of(c), tiles_of[c] * TILE, names,
                               to_ring=True):
                copy.start()

        def update(j, base, slot):
            at, sub = pl.ds(j, 1), pl.ds(rows_ref[j] - base, 1)
            g = {f: grad_refs[f][at, :] for f in families}
            if inv_ref is not None:
                g = {f: x * inv_ref[j] for f, x in g.items()}
            new = access.apply_push({f: ring[f][slot, sub, :] for f in names},
                                    g)
            for f in written:
                ring[f][slot, sub, :] = new[f]

        def take(c, _, reused):
            """Copy ``c``: updated and on its way back, the read of the
            copy ``ahead`` behind it started first.  What costs here is
            control flow (v5e micro, PERF.md section 6, PR 48: a branch
            or a loop's turn ~20 ns, taken or not; a DMA's start or wait
            a few) — so a copy has none but the loop over the rows behind
            its first."""
            read(c + ahead, reused)
            slot, base, rows = c % depth, row_of(c), tiles_of[c] * TILE
            for copy in copies(slot, 0, rows, names, to_ring=True):
                copy.wait()
            update(first[c], base, slot)
            jax.lax.fori_loop(first[c] + 1, first[c + 1],
                              lambda j, _: update(j, base, slot), None)
            for copy in copies(slot, base, rows, written, to_ring=False):
                copy.start()

        @pl.when(pl.program_id(0) == 0)
        def _():
            copies_ref[0] = 0

        @pl.when(m > 0)
        def _():
            zero = jnp.int32(0)

            def cut_tile(i, carry):     # Mosaic unrolls no loop in part
                for k in range(TILE):
                    carry = cut(i * TILE + k, carry)
                return carry
            # no tile in front of the step's first row
            count, _, _ = jax.lax.fori_loop(0, -(-m // TILE), cut_tile,
                                            (zero, zero, jnp.int32(-2)))

            def lead(c, _):
                # every copy starts the read of the copy ``ahead`` behind
                # it: behind the last stand as many of one tile, read for
                # nothing ...
                first[count + c] = m
                tiles_of[count + c] = 1
                # ... and in front of the first, the reads of as many
                read(c, reused=False)
            jax.lax.fori_loop(0, ahead, lead, None)
            # a ring slot's first turn has no write to await
            turn = jnp.minimum(count, depth - ahead)
            jax.lax.fori_loop(0, turn,
                              functools.partial(take, reused=False), None)
            jax.lax.fori_loop(turn, count,
                              functools.partial(take, reused=True), None)

            def unread(c, _):
                for copy in copies(c % depth, 0, TILE, names, to_ring=True):
                    copy.wait()
            jax.lax.fori_loop(count, count + ahead, unread, None)

            def drained(c, _):
                for copy in copies(c % depth, 0, tiles_of[c] * TILE, written,
                                   to_ring=False):
                    copy.wait()
            jax.lax.fori_loop(jnp.maximum(count + ahead - depth, 0), count,
                              drained, None)
            copies_ref[0] += count

    def head_block(i, n_ref):
        # a step behind the head asks for the head's last block again:
        # the pipeline copies nothing for it
        return jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0) // block)

    def padded(x):
        return jnp.pad(x, (0, n_blocks * block - B))

    in_specs = [pl.BlockSpec((block,), lambda i, n_ref: (head_block(i, n_ref),),
                             memory_space=pltpu.SMEM)]
    operands = [padded(rows)]
    if inv is not None:
        in_specs.append(in_specs[0])
        operands.append(padded(inv[:, 0]))
    for f in families:
        in_specs.append(pl.BlockSpec(
            (block, grads[f].shape[1]),
            lambda i, n_ref: (head_block(i, n_ref), 0)))
        operands.append(grads[f])
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(names)
    operands += [fields[f] for f in names]
    first_field = len(operands) - len(names) + 1     # after ``n``
    *new, copies = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_blocks,), in_specs=in_specs,
            out_specs=[*[pl.BlockSpec(memory_space=pl.ANY)] * len(names),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[
                *(pltpu.VMEM((depth, RUN * TILE, fields[f].shape[1]),
                             jnp.float32) for f in names),
                pltpu.SemaphoreType.DMA((len(names), depth)),
                pltpu.SemaphoreType.DMA((len(names), depth)),
                pltpu.SMEM((block + ahead,), jnp.int32),
                pltpu.SMEM((block + ahead,), jnp.int32)]),
        out_shape=[*(jax.ShapeDtypeStruct(fields[f].shape, fields[f].dtype)
                     for f in names),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        input_output_aliases={first_field + k: k for k in range(len(names))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 2 * vmem)),
        name="rmw_tiles",
    )(jnp.reshape(n, (1,)).astype(jnp.int32), *operands)
    return dict(zip(names, new)), copies[0]
