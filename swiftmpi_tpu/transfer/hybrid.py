"""``hybrid`` transfer backend: replicated hot head + sharded cold tail.

Zipf-aware placement (Parallax, arXiv:1808.02621): real vocabularies put
most of the per-step traffic on a tiny frequency head, which under the pure
``tpu`` backend inflates the routed-row count and skews bucket occupancy
(the overflow counter measures exactly this).  The hybrid backend splits
the unified slot space the ``HotColdPartition`` defines:

* **hot** (``slot < n_hot``): rows live REPLICATED on every device as the
  ``field + "@hot"`` state arrays.  Pull is a local ``take`` — zero
  cross-chip bytes.  Push scatter-adds the local batch slice into an
  ``(n_hot, width)`` dense buffer and reconciles with a SINGLE dense
  ``psum`` over the whole mesh — no routing, no dedup sort (SparCML's
  "densify once occupancy crosses the threshold", arXiv:1802.08021,
  applied per-partition via ``calibrate_hot_k``).
* **tail** (``slot >= n_hot``): rows stay in the hash-sharded table and
  route through the unmodified :class:`TpuTransfer` all_to_all path,
  re-based by ``-n_hot``.

The composition sits behind the same ``pull``/``push``/``push_span`` API
(including the PR-2 stencil span wire format), so models consume the split
transparently.  Per-step traffic (routed tail rows, hot rows, psum bytes,
bucket overflow) is accounted with the same tracer/eager discipline as the
tpu backend's overflow counter and read via :meth:`traffic`.

A state dict with no ``@hot`` fields (n_hot == 0, e.g. the LR loop, which
has no upfront frequency histogram) degenerates to the pure tail path —
``hybrid`` is then bit-identical to ``tpu``.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from swiftmpi_tpu.cluster.mesh import SHARD_AXIS
from swiftmpi_tpu.parameter.sparse_table import (base_field, hot_name,
                                                 is_hot_field)
from swiftmpi_tpu.transfer.api import Transfer
from swiftmpi_tpu.transfer.tpu import TpuTransfer


class HybridTransfer(Transfer):
    name = "hybrid"

    def __init__(self, mesh: Mesh, axis: str = SHARD_AXIS,
                 bucket_capacity: Optional[int] = None,
                 debug_overflow: bool = False):
        self.mesh = mesh
        self.axis = axis
        self.tail = TpuTransfer(mesh, axis, bucket_capacity, debug_overflow)
        self._hot_push_cache: Dict = {}
        self._hot_total = 0
        self._psum_bytes_total = 0
        self._hot_pending: list = []

    def on_membership(self, epoch: int, live_ranks) -> None:
        """Elastic membership (api.py): the tail backend owns most of
        the world-shaped compiled state, so it is told FIRST (its own
        epoch guard runs there), then the hybrid books the epoch and
        drops its hot-psum cache."""
        self.tail.on_membership(epoch, live_ranks)
        super().on_membership(epoch, live_ranks)

    def _membership_changed(self) -> None:
        self._hot_push_cache.clear()

    # -- attribute forwarding to the tail backend --------------------------
    @property
    def metrics(self):
        return self.tail.metrics

    @metrics.setter
    def metrics(self, m):
        self.tail.metrics = m

    @property
    def count_traffic(self) -> bool:
        return self.tail.count_traffic

    @count_traffic.setter
    def count_traffic(self, flag: bool):
        self.tail.count_traffic = bool(flag)

    @property
    def bucket_capacity(self):
        return self.tail.bucket_capacity

    @property
    def window_expected_unique(self):
        """Expected-unique-rows hint for the window wire-format crossover
        (see TpuTransfer); lives on the tail, which makes the decision."""
        return self.tail.window_expected_unique

    @window_expected_unique.setter
    def window_expected_unique(self, v):
        self.tail.window_expected_unique = v

    @property
    def wire_quant(self) -> str:
        """Window value-quantization mode (``off|int8|bf16``); lives on
        the tail, which makes the wire-format decision and owns the EF
        drain.  Hot rows are untouched — their dense psum never
        quantizes."""
        return self.tail.wire_quant

    @wire_quant.setter
    def wire_quant(self, v: str):
        self.tail.wire_quant = v

    @property
    def wire_quant_guard(self) -> float:
        return self.tail.wire_quant_guard

    @wire_quant_guard.setter
    def wire_quant_guard(self, v: float):
        self.tail.wire_quant_guard = float(v)

    @property
    def wire_sketch(self) -> bool:
        """Counting-sketch wire rung arm (``sparse_sketch``); lives on
        the tail, whose window plan prices the ladder.  Hot rows are
        untouched — their dense psum ships no index stream at all."""
        return self.tail.wire_sketch

    @wire_sketch.setter
    def wire_sketch(self, v: bool):
        self.tail.wire_sketch = bool(v)

    @property
    def pull_quant(self) -> str:
        """Pull value-quantization mode (``off|int8|bf16``); lives on
        the tail, whose pull plan prices the format.  Hot rows are
        untouched — replica reads ship nothing and are never
        quantized."""
        return self.tail.pull_quant

    @pull_quant.setter
    def pull_quant(self, v: str):
        self.tail.pull_quant = v

    @property
    def pull_quant_guard(self) -> float:
        return self.tail.pull_quant_guard

    @pull_quant_guard.setter
    def pull_quant_guard(self, v: float):
        self.tail.pull_quant_guard = float(v)

    @property
    def pull_cache(self) -> int:
        """Versioned pull-cache line count (0 = off); lives on the
        tail, which runs the cache shadow — hot-replica hits are
        already 0 bytes and never enter the cache."""
        return self.tail.pull_cache

    @pull_cache.setter
    def pull_cache(self, v: int):
        self.tail.pull_cache = int(v)

    @property
    def pull_cache_oracle(self) -> bool:
        return self.tail.pull_cache_oracle

    @pull_cache_oracle.setter
    def pull_cache_oracle(self, v: bool):
        self.tail.pull_cache_oracle = bool(v)

    def pull_shadow_flush(self) -> None:
        # the tail owns the live shadow (tail pulls book the cache);
        # flush both for symmetry with the knob forwarding above
        self.tail.pull_shadow_flush()
        super().pull_shadow_flush()

    @property
    def collective_mode(self) -> str:
        """Hot/dense collective selection mode (``psum | auto |
        sparse_allreduce``); storage lives on the tail so the tail's
        window plan (dense rung) and the hybrid's hot plan — both
        compiled via transfer/plan.py — read the same knob."""
        return self.tail.collective_mode

    @collective_mode.setter
    def collective_mode(self, v: str):
        self.tail.collective_mode = v

    @property
    def hot_touched_fraction(self):
        return self.tail.hot_touched_fraction

    @hot_touched_fraction.setter
    def hot_touched_fraction(self, v):
        self.tail.hot_touched_fraction = v

    @property
    def sparse_ar_ratio(self) -> float:
        return self.tail.sparse_ar_ratio

    @sparse_ar_ratio.setter
    def sparse_ar_ratio(self, v: float):
        self.tail.sparse_ar_ratio = float(v)

    def wire_dense_ratio(self, family=None):
        return self.tail.wire_dense_ratio(family)

    def set_wire_dense_ratio(self, ratio, family=None):
        # the tail backend asks the wire-format question (its
        # _push_window_flat), so the tunable ratio state lives there
        self.tail.set_wire_dense_ratio(ratio, family)

    def overflow_count(self) -> int:
        return self.tail.overflow_count()

    # -- hot/tail split helpers --------------------------------------------
    @staticmethod
    def _n_hot(state) -> int:
        for f, v in state.items():
            if is_hot_field(f):
                return int(v.shape[0])
        return 0

    @staticmethod
    def _split_state(state):
        tail = {f: v for f, v in state.items() if not is_hot_field(f)}
        hot = {base_field(f): v for f, v in state.items()
               if is_hot_field(f)}
        return tail, hot

    # -- traffic accounting ------------------------------------------------
    def _accum_hot(self, psum_bytes: int, hot) -> None:
        self._hot_total += int(hot)
        self._psum_bytes_total += int(psum_bytes)
        self._obs_inc("hot_rows", int(hot))
        self._obs_inc("psum_bytes", int(psum_bytes))

    def _record_hot(self, hot, psum_bytes: int) -> None:
        cb = partial(self._accum_hot, int(psum_bytes))
        if isinstance(hot, jax.core.Tracer):
            jax.debug.callback(cb, hot)
        else:
            self._hot_pending.append((int(psum_bytes), hot))
            if len(self._hot_pending) >= 1024:
                pending, self._hot_pending = self._hot_pending, []
                for b, h in pending:
                    self._accum_hot(b, h)

    def _accum_hot_sparse(self, row_bytes: int, hot) -> None:
        # sparse-allreduce twin of _accum_hot: the byte volume depends
        # on the TRACED touched-row count (touched * per-row bytes),
        # not the static head size, so it is computed in the callback
        self._accum_hot(int(hot) * int(row_bytes), hot)

    def _record_hot_sparse(self, hot, row_bytes: int) -> None:
        cb = partial(self._accum_hot_sparse, int(row_bytes))
        if isinstance(hot, jax.core.Tracer):
            jax.debug.callback(cb, hot)
        else:
            self._accum_hot_sparse(int(row_bytes), hot)

    def traffic(self) -> Dict[str, int]:
        """Cumulative per-step traffic counters (counted while
        ``count_traffic`` is set): ``routed_rows`` (tail rows through
        all_to_all), ``hot_rows`` (head hits served dense), ``psum_bytes``
        (dense reconciliation volume), ``overflow_dropped``."""
        jax.effects_barrier()
        pending, self._hot_pending = self._hot_pending, []
        for b, h in pending:
            self._accum_hot(b, h)
        t = self.tail.traffic()
        w = self.wire_traffic()       # own ledger: hot-psum exchanges
        out = {"routed_rows": t["routed_rows"],
               "hot_rows": self._hot_total,
               "psum_bytes": self._psum_bytes_total,
               "overflow_dropped": t["overflow_dropped"]}
        for k in ("wire_bytes", "dispatches", "window_sparse",
                  "window_dense", "window_fmt_dense", "window_fmt_sparse",
                  "window_fmt_q", "window_fmt_bitmap", "window_fmt_sketch",
                  "collective_psum", "collective_sparse_ar",
                  "hot_psum_bytes_saved",
                  "plan_compiles", "plan_cache_hits",
                  "coalesced_rows_in", "coalesced_rows_out",
                  "pull_bytes", "pull_rows", "pull_hot_rows",
                  "pull_cache_hits", "pull_delta_rows",
                  "pull_bytes_saved",
                  "pull_fmt_full", "pull_fmt_bf16", "pull_fmt_q"):
            out[k] = t.get(k, 0) + w.get(k, 0)
        if self.metrics is not None:
            self.metrics.set("transfer_hot_rows", out["hot_rows"])
            self.metrics.set("transfer_psum_bytes", out["psum_bytes"])
        return out

    def _batch_divisor(self) -> int:
        """The tail path shard_maps the batch dim over the mesh's data and
        shard axes; request lengths must divide their product."""
        div = int(self.mesh.shape[self.axis])
        if self.tail.dp_axis:
            div *= int(self.mesh.shape[self.tail.dp_axis])
        return div

    def _pad_batch(self, slots, grads=None, counts=None):
        """Pad the batch dim to the next mesh multiple with -1 slots
        (dropped by both the routed and dense paths) and zero grad rows.
        Stencil spans are B + 2W rows — almost never mesh-aligned — so
        the backend absorbs the alignment instead of every caller.
        Returns ``(slots, grads, counts, orig_len)``."""
        B = slots.shape[0]
        pad = (-B) % self._batch_divisor()
        if pad == 0:
            return slots, grads, counts, B
        slots = jnp.concatenate(
            [slots, jnp.full((pad,) + slots.shape[1:], -1, slots.dtype)])
        if grads is not None:
            grads = {f: jnp.concatenate(
                [g, jnp.zeros((pad,) + g.shape[1:], g.dtype)])
                for f, g in ((f, jnp.asarray(g)) for f, g in grads.items())}
        if counts is not None:
            counts = jnp.concatenate(
                [jnp.asarray(counts, jnp.float32),
                 jnp.zeros((pad,), jnp.float32)])
        return slots, grads, counts, B

    # -- pull --------------------------------------------------------------
    # No override: the base-class pull interpreter (api.Transfer.pull)
    # drives this backend through its ``hot_split`` placement stage
    # (``_interpret_pull_hot_split``), composing `_pad_batch`,
    # `_split_state` and the tail backend's own pull — replica hits
    # resolve locally at 0 bytes, tail rows book (and cache/quantize)
    # on the tail's ledger and merge in traffic().

    # -- push --------------------------------------------------------------
    def push(self, state, slots, grads, access, mean=False, counts=None):
        slots = jnp.asarray(slots, jnp.int32)
        slots, grads, counts, _ = self._pad_batch(slots, grads, counts)
        tail_state, hot_state = self._split_state(state)
        n_hot = self._n_hot(state)
        if n_hot == 0:
            return self.tail.push(tail_state, slots, grads, access,
                                  mean=mean, counts=counts)
        is_hot = (slots >= 0) & (slots < n_hot)
        tail_slots = jnp.where(slots >= n_hot, slots - n_hot, -1)
        new_tail = self.tail.push(tail_state, tail_slots, grads, access,
                                  mean=mean, counts=counts)
        if self.count_traffic:
            width_bytes = sum(
                np.dtype(jnp.asarray(g).dtype).itemsize * g.shape[1]
                for g in grads.values()) + 4        # + f32 counts column
            self._record_hot(jnp.sum(is_hot), n_hot * width_bytes)
            # wire ledger: the hot psum is one dispatch shipping the full
            # replicated head (dense; token keeps the rows value traced)
            self._record_exchange(jnp.sum(is_hot) * 0 + n_hot, width_bytes)
        new_hot = self._hot_push(hot_state, slots, grads, access,
                                 mean, counts)
        out = dict(new_tail)
        out.update({hot_name(f): v for f, v in new_hot.items()})
        return out

    def push_span(self, state, slots, grads, counts, access, mean=False):
        """Span push (stencil wire format): rows carry window-overlap
        gradient SUMS with per-row data counts; both paths normalize by
        the summed data counts, matching ``XlaTransfer.push_span``."""
        return self.push(state, slots, grads, access, mean=mean,
                         counts=counts)

    # -- window-coalesced push ---------------------------------------------
    # No override: the base-class TrafficPlan interpreter
    # (api.Transfer.push_window) drives the window path through its
    # ``hot_split`` placement stage, which composes this backend's
    # structural primitives — `_pad_batch`, `_split_state`, the tail's
    # dedup/exchange primitives, and `_hot_push` below.

    def _hot_push(self, hot_state, slots, grads, access, mean, counts):
        with_counts = counts is not None
        sig = (self.tail._signature(hot_state, slots, grads),
               mean, with_counts)
        fn = self._hot_push_cache.get(sig)
        if fn is None:
            from swiftmpi_tpu.obs import costs as obs_costs
            fn = self._hot_push_cache.setdefault(
                sig, obs_costs.track("hybrid_hot_push", jax.jit(
                    self._build_hot_push(
                        hot_state, access, tuple(sorted(grads)), mean,
                        with_counts))))
        if with_counts:
            return fn(hot_state, slots, grads,
                      jnp.asarray(counts, jnp.float32))
        return fn(hot_state, slots, grads)

    def _build_hot_push(self, hot_state, access, grad_fields, mean,
                        with_counts):
        n_hot = next(iter(hot_state.values())).shape[0]
        bspec = self.tail._batch_spec()
        axes = (self.tail.dp_axis, self.axis) if self.tail.dp_axis \
            else (self.axis,)
        state_specs = {f: P() for f in hot_state}
        grad_specs = {f: bspec for f in grad_fields}
        in_specs = (state_specs, bspec, grad_specs)
        if with_counts:
            in_specs += (bspec,)

        @partial(jax.shard_map, mesh=self.mesh, in_specs=in_specs,
                 out_specs=state_specs, check_vma=False)
        def _hot(hot_l, slots_l, grads_l, *maybe_counts):
            valid = (slots_l >= 0) & (slots_l < n_hot)
            # tail and padding slots scatter out-of-bounds and drop
            safe = jnp.where(valid, slots_l, n_hot)
            if with_counts:
                c = maybe_counts[0] * valid
            else:
                c = valid.astype(jnp.float32)
            acc = {}
            for f in grad_fields:
                g = jnp.asarray(grads_l[f])
                acc[f] = jnp.zeros((n_hot, g.shape[1]), g.dtype).at[
                    safe].add(g, mode="drop")
            csum = jnp.zeros((n_hot,), jnp.float32).at[safe].add(
                c, mode="drop")
            # the whole reconciliation is this one dense psum: no
            # routing, no dedup sort — duplicate hot slots summed by the
            # scatter, cross-device duplicates summed by the reduction
            acc, csum = jax.lax.psum((acc, csum), axes)
            if mean:
                inv = (1.0 / jnp.maximum(csum, 1.0))[:, None]
                acc = {f: a * inv for f, a in acc.items()}
            new_fields = access.apply_push(hot_l, acc)
            out = dict(hot_l)
            out.update(new_fields)
            return out

        return _hot

    def _hot_push_sparse(self, hot_state, slots, grads, access, mean,
                         counts):
        """Sparse-allreduce hot-plane reconcile (the plan interpreter
        dispatches here when the hot TrafficPlan's collective says so —
        this backend never reads the collective name itself)."""
        with_counts = counts is not None
        sig = (self.tail._signature(hot_state, slots, grads),
               mean, with_counts, "sparse_ar")
        fn = self._hot_push_cache.get(sig)
        if fn is None:
            from swiftmpi_tpu.obs import costs as obs_costs
            fn = self._hot_push_cache.setdefault(
                sig, obs_costs.track("hybrid_hot_push_sparse", jax.jit(
                    self._build_hot_push_sparse(
                        hot_state, access, tuple(sorted(grads)), mean,
                        with_counts))))
        if with_counts:
            return fn(hot_state, slots, grads,
                      jnp.asarray(counts, jnp.float32))
        return fn(hot_state, slots, grads)

    def _build_hot_push_sparse(self, hot_state, access, grad_fields,
                               mean, with_counts):
        """Ok-Topk split-and-exchange for the replicated hot head
        (transfer/sparse_allreduce): each shard scatter-adds its local
        touched rows into a bucket-PERMUTED dense accumulator (row r →
        bucket r % n, so the frequency-ranked Zipf head spreads evenly
        over shards), a tiled ``psum_scatter`` over the permuted layout
        is the balanced reduce-scatter merging duplicate indices, and
        an ``all_gather`` + unpermute is the sparse allgather
        rebroadcasting the reduced rows to every replica.  Semantically
        identical to the dense psum up to float reduction order (the
        parity test pins allclose, not bit-identity); the wire ledger
        books the touched-row payload a variable-length wire ships —
        see the module docstring of transfer/sparse_allreduce."""
        from swiftmpi_tpu.transfer.sparse_allreduce import (
            bucket_layout, bucket_permute, bucket_unpermute)
        n_hot = next(iter(hot_state.values())).shape[0]
        n = int(self.mesh.shape[self.axis])
        cap_bucket, n_pad = bucket_layout(n_hot, n)
        bspec = self.tail._batch_spec()
        dp_axis = self.tail.dp_axis
        state_specs = {f: P() for f in hot_state}
        grad_specs = {f: bspec for f in grad_fields}
        in_specs = (state_specs, bspec, grad_specs)
        if with_counts:
            in_specs += (bspec,)

        def _reduce_bucketed(plane):
            # permuted layout → tiled psum_scatter IS the balanced
            # reduce-scatter over row-hash buckets; the all_gather is
            # the sparse allgather back to the replicated head
            b = bucket_permute(plane, n)
            b = jax.lax.psum_scatter(b, self.axis, scatter_dimension=0,
                                     tiled=True)
            if dp_axis:
                b = jax.lax.psum(b, dp_axis)
            g = jax.lax.all_gather(b, self.axis, axis=0, tiled=True)
            return bucket_unpermute(g, n)[:n_hot]

        @partial(jax.shard_map, mesh=self.mesh, in_specs=in_specs,
                 out_specs=state_specs, check_vma=False)
        def _hot_sparse(hot_l, slots_l, grads_l, *maybe_counts):
            valid = (slots_l >= 0) & (slots_l < n_hot)
            # tail and padding slots scatter out-of-bounds and drop;
            # pad rows [n_hot, n_pad) are never touched and contribute
            # exact zeros through the exchange
            safe = jnp.where(valid, slots_l, n_pad)
            if with_counts:
                c = maybe_counts[0] * valid
            else:
                c = valid.astype(jnp.float32)
            acc = {}
            for f in grad_fields:
                g = jnp.asarray(grads_l[f])
                local = jnp.zeros((n_pad, g.shape[1]), g.dtype).at[
                    safe].add(g * valid[:, None].astype(g.dtype),
                              mode="drop")
                with jax.named_scope("wire_exchange"):
                    acc[f] = _reduce_bucketed(local)
            csum = _reduce_bucketed(
                jnp.zeros((n_pad,), jnp.float32).at[safe].add(
                    c, mode="drop"))
            if mean:
                inv = (1.0 / jnp.maximum(csum, 1.0))[:, None]
                acc = {f: a * inv for f, a in acc.items()}
            new_fields = access.apply_push(hot_l, acc)
            out = dict(hot_l)
            out.update(new_fields)
            return out

        return _hot_sparse
