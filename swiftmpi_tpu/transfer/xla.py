"""``xla`` transfer backend: gather/scatter on the rows a device holds.

The idiomatic-JAX data plane: ``pull`` is a row gather, ``push`` is an
in-batch segment-sum dedup followed by a one-shot access-method update and a
row scatter.  On a table row-sharded over a mesh axis the same gather and
the same sparse push run on each row's OWNER, reached through
``transfer/route.py``'s ``all_to_all`` exchange (`XlaTransfer.route_mode`
says when; a mesh the backend was not told of, or one with a ``data`` axis,
leaves the exchange to the partitioner as before).  Everything here is
shape-static and traceable.

Dedup-without-unique trick (XLA has no dynamic ``unique``): sort the batch
slots, segment-sum gradients into batch-local segments keyed by
sorted-adjacency, and scatter one combined update per segment.  Cost is
O(B log B + B·d) regardless of table capacity.

``dense_apply=True`` switches push to a full-table dense update (scatter the
summed grads into a (capacity, d) zero array, then apply the access method
to the whole table).  Untouched rows see zero grad and are bit-identical
no-ops for any sane access rule; this trades HBM bandwidth for zero scatter
irregularity and can win for small tables.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from swiftmpi_tpu import obs
from swiftmpi_tpu.parameter.sparse_table import is_hot_field
from swiftmpi_tpu.transfer import route, tile_rmw
from swiftmpi_tpu.transfer.api import (Transfer, bump_row_versions,
                                       grad_row_bytes)

# Row write-back of the sparse push (`_set_rows`): XLA's TPU scatter has
# two costs, and the `indices_are_sorted` hint alone picks between them
# (v5e micro A/B on one donated f32[2340001, 300] field, PERF.md section
# 6, PR 30; `unique_indices`, `mode`, in-bounds padding and an
# optimization barrier around the values change nothing; re-taken in PR
# 32 on the row-major field a 300-wide row is now stored as, 384 lanes
# and 3.59 GB: `scripts/writeback_micro.py --layout row_major`).  With
# the hint the scatter streams the WHOLE field through the chip, 11.5 ms
# there (3.2 ps a byte of stored field) plus ~22 ns a slot; without it
# the rows are written one by one, ~120 ns a slot, dropped padding
# included, and nothing is fixed.  One row written alone therefore costs
# what sweeping this many bytes of field costs, and the cheaper form
# follows from the shapes: ~116,000 slots are the crossover on that field
# (measured: 13.98 against 14.90 ms at 122,880 slots, 12.84 against
# 14.18 at 110,000).
#
# What the constant still decides (ROADMAP D10).  From static shapes, the
# form of every push `write_back_form` does not answer ``tiles`` for:
# one-wide logistic rows, widths that keep the column-major default,
# fields that are not f32, a table split by the partitioner, the CPU.
_ROW_WRITE_AS_SWEPT_BYTES = 31_000

# The same weighing for the tile kernel (`_rmw_tiles`, PR 47; a run of
# adjacent named tiles one copy since PR 48), which has no price a slot:
# ~35 ns a distinct row + ~83 ns a copy where a push touches a parameter
# and its accumulator (v5e micro, PERF.md section 6, PR 48:
# `scripts/writeback_micro.py --width 384 --cases runs`; 117 ns a row at
# PR 47, ~2 x 107 in the loop of gathers and scatters before it), against
# the sweep's 11.5 ms a field + ~16 ns a slot.  The cells' pushes cost a
# fraction of their sweeps (a cbow2m-b16k target push, 145 K rows of
# 180,224 slots: 9.3 ms, two sweeps 28.7).  The distinct rows show only
# at run time and the form is chosen from shapes, so a slot is weighed at
# the kernel's dearest — every slot a distinct row, most tiles named, one
# field (two halve the control flow a row-field and double the sweep:
# 1.1 M rows of 1.1 M slots 36.0 ms, two sweeps 58.5) — where the memory
# binds and a row costs 28 ns (585,000 rows of one field 19.8 ms the
# kernel, 21.0 the sweep; 1,100,000: 34.3 and 29.3): 12 ns over the
# sweep's own 16 a slot, and the two cross at ~678,000 slots on a 3.59 GB
# field, what sweeping this many bytes costs.  ~283,000 on an owner's
# 1.5 GB shard (the cells' longest pushes: 180,224 and 137,536).
_TILE_SLOT_AS_SWEPT_BYTES = 5_300

# Gradients of a push from which `_push_rows` orders the push behind the
# state it is given (an `optimization_barrier`, for the step's peak memory
# alone).  Below, a batch is too small to move the peak of a 16 GB chip —
# and the barrier is an OPEN SAFETY ITEM (ROADMAP D10): with it,
# cbow2m-demo's step (pushes of 8.4 and 1.2 MB) HANGS the v5e, with the
# kernel or with `per_row`'s own barrier (`_after`) in its place, and runs
# without (PERF.md section 6, PR 47; `scripts/barrier_hang_repro.py`
# reproduces it: a barrier over the fields alone runs there, over the
# gradients alone too, over both in one — the tie that orders — hangs).
# The cells whose pushes carry 31 MB and more run with
# it; nothing between 8.4 and 31 MB has run on the chip either way, and
# the constant stands between the two for no better reason.  Which pushes
# it orders: `tests/test_write_back.py`.
_ORDERED_PUSH_BYTES = 16 << 20


def _masked_gather(arr: jax.Array, slots: jax.Array,
                   valid: jax.Array) -> jax.Array:
    # clip: an out-of-range slot is a caller bug, but TPU OOB gather yields
    # garbage/NaN rather than trapping — clamp so it stays observable as a
    # wrong row, not as NaN contamination.
    safe = jnp.clip(jnp.where(valid, slots, 0), 0, arr.shape[0] - 1)
    rows = jnp.take(arr, safe, axis=0)
    return jnp.where(valid[:, None], rows, 0)


def _set_rows(field: jax.Array, rows: jax.Array, values: jax.Array,
              sweep: bool) -> jax.Array:
    """``field`` with ``values[i]`` at row ``rows[i]``: distinct in-range
    rows, and ``capacity`` padding, which drops.  ``sweep`` (ascending
    ``rows`` only) is `XlaTransfer.write_back_form`'s choice of cost; the
    rows and values written are the same."""
    return field.at[rows].set(values, mode="drop", unique_indices=True,
                              indices_are_sorted=sweep)


def _rmw_rows(fields: dict, rows: jax.Array, grads: dict, access,
              sweep: bool, inv=None) -> dict:
    """The push's read-modify-write at ``rows`` (distinct in-range rows,
    and ``capacity`` padding, which reads row 0 and drops): gather the
    touched ``fields``' rows, `access.apply_push` them with ``grads``
    (times ``inv``, a mean push's ``1 / count`` a row, where the caller
    left that to be fused in here), write the updated ones back
    (`_set_rows`)."""
    capacity = next(iter(fields.values())).shape[0]
    safe = jnp.where(rows < capacity, rows, 0)
    current = {f: jnp.take(x, safe, axis=0) for f, x in fields.items()}
    if inv is not None:
        grads = {f: g * inv for f, g in grads.items()}
    updated = access.apply_push(current, grads)
    return {f: _set_rows(x, rows, updated[f], sweep) if f in updated else x
            for f, x in fields.items()}


def _rmw_tiles(fields: dict, rows: jax.Array, grads: dict, access,
               n: jax.Array, inv=None) -> tuple:
    """`_rmw_rows` for ascending ``rows`` whose ``n`` valid ones stand at
    the head, ``capacity`` behind them: the head alone is read, updated
    and written, by whole 8-row tiles (`tile_rmw.rmw_tiles`, one kernel
    for all ``fields``), at the cost of the push's distinct rows and not
    of its slots.  The tile of the last ``capacity % 8`` rows reaches past
    the fields, so those rows, the last of the head, are written row by
    row.  Same rows, same values as `_rmw_rows`; beside them, the copies
    the kernel started one way a field."""
    B = rows.shape[0]
    capacity = next(iter(fields.values())).shape[0]
    whole = capacity - capacity % tile_rmw.TILE
    if whole == capacity:
        return tile_rmw.rmw_tiles(fields, rows, grads, access, n, inv)
    rest = min(capacity - whole, B)
    n_whole = jnp.sum(rows < whole, dtype=jnp.int32)
    fields, copies = tile_rmw.rmw_tiles(fields, rows, grads, access, n_whole,
                                        inv)
    # the ``rest`` slots from the last whole tile's on: a short batch's
    # start early, and the slots in front are the kernel's
    at = jnp.minimum(n_whole, B - rest)

    def cut(x):
        return jax.lax.dynamic_slice_in_dim(x, at, rest)
    mine = at + jnp.arange(rest, dtype=jnp.int32) >= n_whole
    return _rmw_rows(fields, jnp.where(mine, cut(rows), capacity),
                     {f: cut(g) for f, g in grads.items()}, access,
                     sweep=False,
                     inv=None if inv is None else cut(inv)), copies


def _after(x, done):
    """``x``, not to be touched before ``done`` exists (``None``: no
    wait): orders two uses of whole fields that share no data."""
    return x if done is None else jax.lax.optimization_barrier((done, x))[1]


def _ordered(grads: dict) -> bool:
    """Whether `_push_rows` orders a ``tiles`` push of ``grads`` behind
    the state it is given (`_ORDERED_PUSH_BYTES`)."""
    return sum(g.size * g.dtype.itemsize
               for g in grads.values()) >= _ORDERED_PUSH_BYTES


class XlaTransfer(Transfer):
    name = "xla"

    def __init__(self, dense_apply: bool | None = None, shards: int = 1,
                 platform: str | None = None, mesh=None,
                 axis: str | None = None):
        """``dense_apply``: True forces the dense full-table push, False
        forces the sort-based sparse push, None (default) picks per call —
        dense when the push batch is at least half the table capacity.
        At that point the sparse path's sort + per-row gather/scatter
        irregularity costs more than sweeping the table once (the
        crossover is measured in docs/ARCHITECTURE.md; word2vec-scale
        batches over demo-conf-scale tables land far on the dense side).

        ``shards``: how many devices the table's rows are split over
        (``Cluster`` passes its server count).  A row-sharded scatter
        runs on every shard, with the whole batch against ``capacity /
        shards`` rows, so `write_back_form` weighs a shard's rows.

        ``platform``: of the devices the table lives on (``Cluster``
        passes its devices'; default: this process's first device's).
        The write-back's costs were measured on TPUs, so what they
        select is selected there alone.

        ``mesh``, ``axis``: the mesh the table lives on and the axis its
        rows are split over (``Cluster`` passes its own).  With them, and
        more than one shard, a pull and a sparse push are ROUTED to the
        rows' owners (`route_mode`); without them the exchange is the
        partitioner's."""
        self.dense_apply = dense_apply
        self.shards = max(1, int(shards))
        self.platform = platform or jax.devices()[0].platform
        self.mesh, self.axis = mesh, axis
        #: while a list (`count_routed`), every routed pull and push
        #: traced appends ``(rows routed, bucket slots exchanged)``
        self.routed: list | None = None
        #: field -> ``"per_row"`` | ``"sweep"`` | ``"tiles"``: the form
        #: the write-back of that field's last traced sparse push took
        self.resolved_write_back: dict = {}
        #: while a list (`count_rows_written`), every push traced appends
        #: its writes, an ``int32[3]``: distinct valid rows x fields
        #: touched, the distinct 8-row tiles those rows lie in x fields
        #: where the tile kernel moves them (0 where it does not), and
        #: the copies the kernel starts one way for them x fields
        self.rows_written: list | None = None
        # wire ledger (api.py): XLA chooses the actual collectives, so
        # wire_bytes counts the representation-level payload — sparse:
        # valid rows x (index + grad row); dense: capacity x grad row
        self.count_traffic = False

    @contextlib.contextmanager
    def count_rows_written(self):
        """The list every push traced inside the block appends its
        ``(rows, tiles, tile copies)`` written to (a traced ``int32[3]``
        each): what a step built with telemetry on returns beside its
        loss."""
        self.rows_written = tape = []
        try:
            yield tape
        finally:
            self.rows_written = None

    def _count_rows_written(self, written, touched) -> None:
        """A push's writes onto the tape, if one is held: ``written()``,
        its distinct valid rows, the tiles moved for them and the copies
        that moved them one way, times the fields it touches."""
        if self.rows_written is not None:
            self.rows_written.append(written() * len(touched))

    @contextlib.contextmanager
    def count_routed(self):
        """The list every routed pull and push traced inside the block
        appends its ``(rows routed, bucket slots exchanged)`` to (traced
        int32s): what a step built with telemetry on returns for
        ``routed_rows_per_step`` and ``route_fill_share``."""
        self.routed = tape = []
        try:
            yield tape
        finally:
            self.routed = None

    def _count_routed(self, rows, offered) -> None:
        if self.routed is not None:
            self.routed.append((rows, offered))

    # -- owner routing (transfer/route.py) ---------------------------------
    def route_mode(self, state) -> str | None:
        """How a pull or sparse push of ``state`` reaches the rows: from
        what the call can observe, no option.

        ``None``: directly — one shard, no mesh given, a mesh with a
        second axis of more than one device (a ``data`` axis: each group
        holds a replica, the partitioner reconciles them), a replicated
        hot head in the state, or a call site already under a manual axis
        that is not the table's (hogwild's ``worker`` axis: every worker
        holds the whole table).  ``"inside"``: the caller is already
        manual over the table's axis (a step split over it,
        models/word2vec.py): ``state`` is the chip's shard, the slots its
        share of the batch, and `route` runs as is.  ``"wrap"``: the
        call is wrapped in a ``shard_map`` over the table's axis, which
        hands each chip its shard and a 1/n slice of the batch."""
        if self.mesh is None or self.shards == 1:
            return None
        ctx = jax.sharding.get_abstract_mesh()
        manual = () if ctx.empty else ctx.manual_axes
        if manual:
            return "inside" if self.axis in manual else None
        if any(size > 1 for name, size in self.mesh.shape.items()
               if name != self.axis):
            return None
        if any(is_hot_field(f) or x.shape[0] % self.shards
               for f, x in state.items()):
            return None
        return "wrap"

    def _routed(self, fn, args, sharded_out, n_counts):
        """``fn(*args)`` on every chip of the table's axis, every leaf of
        ``args`` split along its first axis; ``fn`` returns ``(sharded
        results, *counts)``, the counts summed over the chips."""
        def body(*args):
            out, *counts = fn(*args)
            return (out, *(jax.lax.psum(c, self.axis) for c in counts))
        row = P(self.axis)
        return jax.shard_map(
            body, mesh=self.mesh, in_specs=jax.tree.map(lambda _: row, args),
            out_specs=(sharded_out(row), *[P()] * n_counts),
            check_vma=False)(*args)

    def _pad_to_shards(self, slots, *rows):
        """A batch the axis does not divide gets ``-1`` slots, padding by
        the transfer's contract, and zero rows (leaves may be ``None``)."""
        pad = (-slots.shape[0]) % self.shards
        if not pad:
            return (slots, *rows)
        return (jnp.pad(slots, (0, pad), constant_values=-1),
                *jax.tree.map(lambda a: jnp.pad(
                    a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)), rows))

    def _membership_changed(self) -> None:
        """Elastic membership (api.py): XLA keeps no compiled caches
        here (jit re-specializes on its own), but the expected-unique
        hint was derived from the OLD world's vocab-to-shard spread —
        clear it so the window crossover reverts to raw row counts
        until the model re-derives it for the new shape."""
        self.window_expected_unique = None

    # -- pull (global_pull_access.h:28-43 equivalent) ----------------------
    def _prim_pull(self, state, slots, fields):
        # structural gather only — the ledger/format/cache logic lives
        # in the base-class pull interpreter (api.Transfer.pull)
        slots = jnp.asarray(slots, jnp.int32)
        mode = self.route_mode(state) if slots.shape[0] else None
        if mode is None:
            valid = slots >= 0
            return {f: _masked_gather(state[f], slots, valid)
                    for f in fields}
        fields = tuple(fields)

        def fetch(shard, slots):
            return route.pull(shard, slots, fields, self.axis, self.shards,
                              _masked_gather)
        shard = {f: state[f] for f in fields}
        if mode == "inside":
            out, *counts = fetch(shard, slots)
        else:
            n_req = slots.shape[0]
            (slots,) = self._pad_to_shards(slots)
            out, *counts = self._routed(
                fetch, (shard, slots), lambda row: dict.fromkeys(fields, row),
                2)
            out = {f: x[:n_req] for f, x in out.items()}
        self._count_routed(*counts)
        return out

    # -- push (global_push_access.h:26-43 + server.h:159-176) --------------
    def push(self, state, slots, grads, access, mean=False):
        slots = jnp.asarray(slots, jnp.int32)
        capacity = next(iter(state.values())).shape[0]
        # a caller already split over the table's axis (`route_mode`)
        # weighed the whole batch against the whole table before it split
        dense = (self.pushes_dense(slots.shape[0], capacity)
                 and self.route_mode(state) != "inside")
        if dense:
            self._record_exchange(
                capacity, grad_row_bytes(grads, with_index=False))
            return self._push_dense(state, slots, grads, access, mean)
        self._record_exchange(jnp.sum(slots >= 0), grad_row_bytes(grads))
        return self._push_sparse(state, slots, grads, access, mean)

    def pushes_dense(self, n_slots: int, capacity: int) -> bool:
        """Whether a push of ``n_slots`` into ``capacity`` rows takes the
        dense full-table form."""
        if self.dense_apply is not None:
            return self.dense_apply
        # per-call compute crossover through the tunable decision
        # hook: dense once the batch reaches capacity/ratio rows.
        # The seed ratio 2.0 reproduces the measured
        # ``>= capacity // 2`` rule exactly (int(cap / 2.0) ==
        # cap // 2), keeping control-off trajectories bit-identical
        return n_slots >= int(capacity / self.wire_dense_ratio("push_apply"))

    def _push_dense(self, state, slots, grads, access, mean=False):
        capacity = next(iter(state.values())).shape[0]
        valid = slots >= 0
        # OOB scatter indices are dropped by XLA; route padding there.
        safe = jnp.where(valid, slots, capacity)

        def _scatter(g, width):
            acc = jnp.zeros((capacity, width), g.dtype)
            return acc.at[safe].add(g, mode="drop")

        with obs.named_scope("dedup"):
            inv = None
            fuse_count = False
            if mean:
                # Single fp32 grad family: fold the contribution counts
                # into the grads scatter as one extra column — one scatter
                # pass over the batch instead of two.  (fp32 only: a bf16
                # count column goes inexact past 256 occurrences of one
                # key.)
                gs = list(grads.values())
                fuse_count = (len(gs) == 1
                              and jnp.asarray(gs[0]).dtype == jnp.float32)
                if not fuse_count:
                    counts = jnp.zeros((capacity,), jnp.float32).at[
                        safe].add(1.0, mode="drop")
                    inv = (1.0 / jnp.maximum(counts, 1.0))[:, None]
            dense_grads = {}
            for f in grads:
                g = jnp.asarray(grads[f])
                width = state[f].shape[1]
                if fuse_count:
                    g1 = jnp.concatenate(
                        [g, jnp.ones((g.shape[0], 1), g.dtype)], axis=1)
                    acc = _scatter(g1, width + 1)
                    dense_grads[f] = acc[:, :width] / jnp.maximum(
                        acc[:, width:], 1.0)
                else:
                    acc = _scatter(g, width)
                    dense_grads[f] = acc * inv if mean else acc
        self._count_rows_written(
            lambda: jnp.stack([jnp.sum(jnp.zeros((capacity,), jnp.bool_).at[
                safe].set(True, mode="drop"), dtype=jnp.int32), 0, 0]),
            access.touched_fields(grads))
        with obs.named_scope("apply"):
            new_fields = access.apply_push(state, dense_grads)
            out = dict(state)
            out.update(new_fields)
            return bump_row_versions(out, state, safe)

    # -- span push (stencil rendering; see models/word2vec.py) -------------
    def push_span(self, state, slots, grads, counts, access, mean=False,
                  _wire=None):
        """Push of POSITION-INDEXED span rows: every row already carries
        the SUM of its window-overlap contributions (the model folded
        those in by dense shifted sums) and ``counts[i]`` says how many,
        so ``mean=True`` divides a key's summed gradient by its summed
        data count — the per-pair push's divisor, the pairs that named
        the key.  It is the sparse push with that multiplicity: sort the
        ``S`` slots, sum duplicates, write the distinct rows at the head
        back in the form every sparse push takes (`write_back_form`), at
        the cost of the rows and not of the span.  Why sorted: a
        sort-free dedup (scatter-min of positions into a ``(capacity,)``
        plane, unsorted fold, every slot written row by row) measured
        1.7 ms a step slower in cbow2m-b16k's step of 22,400 span slots
        and ~13.7 K distinct rows (PERF.md section 6, PR 36)."""
        slots = jnp.asarray(slots, jnp.int32)
        valid = slots >= 0
        if _wire is not None:
            # window path shipping a compressed representation: book the
            # exchange at ENCODED size (see Transfer.push docstring)
            self._record_exchange(jnp.sum(valid), _wire[0],
                                  base_bytes=_wire[1])
        else:
            self._record_exchange(jnp.sum(valid),
                                  grad_row_bytes(grads, with_counts=True))
        return self._push_sparse(state, slots, grads, access, mean,
                                 counts=jnp.asarray(counts, jnp.float32))

    # -- window-coalesced push ---------------------------------------------
    # No override: the base-class TrafficPlan interpreter
    # (api.Transfer.push_window) drives this backend's window path, and
    # the base `_prim_window_dedup` (single-device representative
    # trick) + `push_span` ARE this backend's primitives — the traced
    # single-device twin the parity tests diff the tpu/hybrid windows
    # against.  The same holds for `_prim_sparse_allreduce`: the base
    # class's single-program scatter-add merge + full-table apply
    # (transfer/sparse_allreduce.merge_rows) is exactly what Ok-Topk's
    # reduce-scatter/allgather degenerates to on one program, so this
    # backend inherits it unchanged.

    def _push_sparse(self, state, slots, grads, access, mean=False,
                     counts=None):
        """``counts``: a row's multiplicity under ``mean`` (a span row is
        a sum of that many contributions, `push_span`); ``None``: one.
        One algorithm — sort, sum the duplicates, divide by the summed
        counts, write the distinct rows back — reached directly when the
        table's axis has one shard and through `route.push` when it has
        more (`route_mode`), where it runs on each owner with the shard
        as a table of its own."""
        if slots.shape[0] == 0:
            return dict(state)
        mode = self.route_mode(state)
        if mode is None:
            out, written = self._push_rows(state, slots, grads, access,
                                           mean, counts, self.shards)
        else:
            out, written = self._push_routed(mode, state, slots, grads,
                                             access, mean, counts)
        self._count_rows_written(lambda: written,
                                 access.touched_fields(grads))
        return out

    def _push_routed(self, mode, state, slots, grads, access, mean, counts):
        """`_push_sparse` through the owners.  A chip sums its own
        duplicates (`_combine`), which also orders its distinct rows by
        owner, and every owner pushes what it is sent into its own shard
        (`_push_rows`, ``shards`` = 1: the write-back weighs the shard's
        rows and takes ``tiles`` where one chip would)."""
        if counts is None and mean:
            counts = (slots >= 0).astype(jnp.float32)

        def combine(slots, grads, counts, capacity):
            rows, _, _, weights, _, sums = self._combine(
                slots, grads, capacity, mean, counts, scale=False)
            return rows, sums, weights

        def owner_push(shard, rows, grads, counts):
            return self._push_rows(shard, rows, grads, access, mean, counts,
                                   shards=1)

        def send(shard, slots, grads, counts):
            return route.push(shard, slots, grads, counts, self.axis,
                              self.shards, combine, owner_push)
        if mode == "inside":
            out, written, *tally = send(state, slots, grads, counts)
        else:
            out, written, *tally = self._routed(
                send, (dict(state), *self._pad_to_shards(
                    slots, dict(grads), counts)),
                lambda row: dict.fromkeys(state, row), 3)
        self._count_routed(*tally)
        return out, written

    def _combine(self, slots, grads, capacity, mean, counts, scale):
        """The sparse push's duplicate reduction: ``slots`` sorted, the
        ``grads`` of equal slots summed.  Returns ``(rep_slots, rep_valid,
        safe_rep, seg_counts, inv, combined)``: the distinct slots
        ascending at the head of ``rep_slots``, ``capacity`` behind them;
        a segment's summed multiplicity and its reciprocal (``None``
        unless ``mean``); the summed rows, times ``inv`` where ``scale``
        asks for the mean here."""
        B = slots.shape[0]
        valid = slots >= 0
        with obs.named_scope("dedup"):
            # Sort so duplicates are adjacent; padding (-1 -> capacity)
            # sorts last and is dropped by OOB scatter below.
            sort_keys = jnp.where(valid, slots, capacity)
            order = jnp.argsort(sort_keys)
            sorted_slots = sort_keys[order]
            # Batch-local segment ids: bump at each new slot value.
            new_seg = jnp.concatenate([
                jnp.ones((1,), jnp.int32),
                (sorted_slots[1:] != sorted_slots[:-1]).astype(jnp.int32)])
            seg_ids = jnp.cumsum(new_seg) - 1  # (B,), in [0, B)
            # One representative slot per segment; unused segments ->
            # capacity.
            rep_slots = jnp.full((B,), capacity, jnp.int32).at[
                seg_ids].set(sorted_slots, mode="drop")
            rep_valid = rep_slots < capacity
            safe_rep = jnp.where(rep_valid, rep_slots, 0)

            seg_counts = inv = None
            if mean:
                # seg_ids ascend (cumsum of non-negatives): tell XLA so
                # the scatter lowering can skip the general collision
                # machinery
                weight = valid[order].astype(jnp.float32) if counts is None \
                    else jnp.where(valid, counts, 0.0)[order]
                seg_counts = jnp.zeros((B,), jnp.float32).at[seg_ids].add(
                    weight, mode="drop", indices_are_sorted=True)
                inv = (1.0 / jnp.maximum(seg_counts, 1.0))[:, None]
            combined = {}
            for f in grads:
                g = jnp.asarray(grads[f])[order]
                width = g.shape[1]
                acc = jnp.zeros((B, width), g.dtype)
                acc = acc.at[seg_ids].add(g, mode="drop",
                                          indices_are_sorted=True)
                combined[f] = acc * inv if mean and scale else acc
        return rep_slots, rep_valid, safe_rep, seg_counts, inv, combined

    def _push_rows(self, state, slots, grads, access, mean, counts, shards):
        """The sparse push on the rows ``state`` holds, split over
        ``shards`` devices by the partitioner (1: all of them here).
        Returns the new state and ``int32[3]``: the distinct valid rows
        written and, where the tile kernel moved them, their tiles and
        the copies it started one way a field for those."""
        capacity = next(iter(state.values())).shape[0]
        B = slots.shape[0]
        # only the fields this push's grad families actually update are
        # gathered and re-scattered (a partial push must not round-trip
        # the untouched fields' rows through HBM for nothing)
        touched = access.touched_fields(grads)
        written = [state[f] for f in touched]
        # the form is weighed for the rows THIS call holds: an owner's
        # shard is a table of its own, whatever the backend was built for
        held, self.shards = self.shards, shards
        try:
            form = self.write_back_form(B, written)
        finally:
            self.shards = held
        self.resolved_write_back.update(dict.fromkeys(touched, form))
        if form == "tiles" and _ordered(grads):
            # A push sums its batch after the state it is given exists,
            # i.e. after the push before it, whichever fields that wrote:
            # left to itself the scheduler sorts and sums one push's batch
            # before another's gradients are computed, one (B, width)
            # buffer more at the step's peak (0.5 GiB of temporaries in
            # cbow2m-b16k's compiled step, PERF.md section 6, PR 34 and
            # PR 47; its `peak_hbm_gb` is held to 1 %).  Every field of
            # the state: a barrier over the touched ones alone, or over
            # the gradients and one element of each field, leaves the
            # step its 1.53 GB of temporaries (0.98 with this one).
            state, grads = jax.lax.optimization_barrier(
                (dict(state), dict(grads)))
        # the tile kernel multiplies where it reads a row: no (B, width)
        # product stands between the sums and the write-back
        rep_slots, rep_valid, safe_rep, _, inv, combined = self._combine(
            slots, grads, capacity, mean, counts, scale=form != "tiles")

        # Unused segments' representatives stay == capacity: OOB, dropped.
        # rep_slots are ascending AND one-per-segment by construction
        # (duplicates exist only among the dropped capacity-fill tail), so
        # any form of the write-back may take them.
        out = dict(state)
        n_rows = jnp.sum(rep_valid, dtype=jnp.int32)
        # ... and, where the kernel moves them, the tiles they lie in
        n_tiles = jnp.sum(rep_valid & (jnp.diff(
            rep_slots // tile_rmw.TILE, prepend=-1) != 0), dtype=jnp.int32)
        written = jnp.stack([n_rows, 0, 0])
        if form != "per_row":
            fields = {f: state[f] for f in touched}
            with obs.named_scope("apply"):
                if form == "sweep":
                    out.update(_rmw_rows(fields, rep_slots, combined, access,
                                         sweep=True))
                else:
                    # ... and the copies that moved those: the kernel's
                    # own count of the runs of adjacent tiles it cut
                    fields, n_copies = _rmw_tiles(
                        fields, rep_slots, combined, access, n_rows, inv=inv)
                    out.update(fields)
                    written = jnp.stack([n_rows, n_tiles, n_copies])
                return bump_row_versions(out, state, rep_slots), written
        # Per row.  Where a field is column-major in HBM (a tall array
        # whose stored width is no multiple of 128: `access.stored_width`
        # widens the rows that can afford it, and then there is nothing
        # to order) XLA reads and writes its rows in a row-major copy of
        # the whole field and, unlike the sweep, does not fuse the read
        # into the write.  One field after the other then, so that no two
        # such copies need be alive at once (left to itself the 300-wide
        # sg2m-b2k step did not fit the chip): the reads first, the last
        # field read is written from the same copy, then the others in
        # turn.  `_after` stands outside the scope: the layout copies it
        # orders are not apply's work and stay under no phase.
        src, current, done = {}, {}, None
        for f in touched:
            src[f] = _after(state[f], done)
            with obs.named_scope("apply"):
                current[f] = done = jnp.take(src[f], safe_rep, axis=0)
        with obs.named_scope("apply"):
            updated = access.apply_push(current, combined)
        for f in reversed(touched):
            if f not in updated:
                continue
            field = src[f] if f == touched[-1] else _after(state[f], done)
            with obs.named_scope("apply"):
                out[f] = done = _set_rows(field, rep_slots, updated[f],
                                          sweep=False)
        with obs.named_scope("apply"):
            return bump_row_versions(out, state, rep_slots), written

    def write_back_form(self, n: int, fields) -> str:
        """How to write ``n`` ascending slots, the distinct valid rows at
        their head, back into each of ``fields``: ``"tiles"``
        (`_rmw_tiles`: one kernel a push, at the cost of the rows' 8-row
        tiles) where every field is one it takes — f32 rows of whole
        128-lane tiles (row-major by the compiler's default, a tile
        contiguous), local to one TPU — and the push is not so long that one sweep of the fields is
        cheaper (`_TILE_SLOT_AS_SWEPT_BYTES`); else the cheaper of
        ``"per_row"`` and ``"sweep"`` for ``n`` slots
        (`_ROW_WRITE_AS_SWEPT_BYTES`).  From static shapes alone."""
        swept = sum((f.shape[0] // self.shards) * f.shape[1]
                    * f.dtype.itemsize for f in fields)
        if (self.shards == 1 and self.platform == "tpu"
                and all(f.shape[1] % 128 == 0 and f.dtype == jnp.float32
                        for f in fields)):
            rows, slot_bytes = "tiles", _TILE_SLOT_AS_SWEPT_BYTES
        else:
            rows, slot_bytes = "per_row", _ROW_WRITE_AS_SWEPT_BYTES
        return rows if n * len(fields) * slot_bytes <= swept else "sweep"
