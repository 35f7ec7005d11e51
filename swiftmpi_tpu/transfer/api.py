"""Transfer layer: the pull/push data plane, with backend selection.

This is the TPU-native replacement for the reference's entire RPC stack —
``Transfer``/``Listener``/``Route`` over ZeroMQ plus the
``GlobalPullAccess::pull_with_barrier`` / ``GlobalPushAccess::
push_with_barrier`` clients (`/root/reference/src/transfer/transfer.h:86-241`,
`/root/reference/src/parameter/global_pull_access.h:28-43`,
`global_push_access.h:26-43`).  Per the BASELINE north star, the interface
survives and the wire disappears: a backend is selected by the ``transfer``
config key and turns pull/push into XLA collectives.

Backends:

* ``xla``   — gather/scatter with sharding constraints; XLA chooses the
              collectives.  Works under any mesh (or none).  Default.
* ``tpu``   — explicit SPMD routing via ``shard_map``: keys are bucketed by
              owning shard, ``all_to_all`` ships requests over ICI, owners
              gather/apply locally, ``all_to_all`` ships rows back.  The
              literal TPU translation of the reference pull/push RPC
              (SURVEY.md §3.2-3.3) on a 1-D ``shard`` mesh.
* ``hybrid`` — Zipf-aware composition: frequency-hot rows replicated on
              every device and reconciled with one dense ``psum`` per
              push, cold-tail rows through the ``tpu`` routing above
              (transfer/hybrid.py; requires a ``HotColdPartition`` on
              the KeyIndex to be more than an alias of ``tpu``).
* ``local`` — numpy golden model of the same semantics, for tests.

Shared semantics (all backends, property-tested against each other):

* ``pull(state, slots) -> rows``: per-position row gather of the access
  method's pull-visible fields; ``slot == -1`` padding yields zero rows.
* ``push(state, slots, grads) -> state'``: duplicate slots' gradients are
  **summed**, then the access method's update is applied **once** per
  unique row.  ``slot == -1`` contributions are dropped.

The reference instead applies one sequential AdaGrad step per *worker* per
key (server.h:159-176) — order-dependent and racy (SURVEY.md §3.3).  The
sum-then-apply-once rule is the deliberate synchronous-SPMD semantic; the
async flavor is recovered at the model layer by taking several local steps
between pushes.

Within-worker mean normalization (the reference's ``grad /= count`` at
serialization, word2vec.h:120-132) stays the caller's job via
``LocalParamCache.normalized_grads`` or the models' count scaling.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from swiftmpi_tpu import obs
from swiftmpi_tpu.parameter.access import AccessMethod
from swiftmpi_tpu.parameter.sparse_table import ROWVER_KEY, TableState
from swiftmpi_tpu.utils.config import ConfigParser


def bump_row_versions(out, state, safe_rows):
    """Device twin of the row-version bump (delta-pull plane): stamp
    the touched rows of the ``@rowver`` plane past the array's current
    max — per-shard monotonic with no host counter, since inside a
    ``shard_map`` the max runs over the local shard slice.  ``out`` is
    the post-apply state dict being built; ``safe_rows`` may carry
    out-of-bounds padding (``== capacity``), which drops.  A no-op
    (and trace-identical) when the plane is absent — the static dict
    check keeps ``pull_cache: off`` programs untouched.  Every push
    apply path MUST route its touched rows through here (or the local
    oracle's numpy twin): the PullCache's version-exact hit contract
    depends on it."""
    if ROWVER_KEY not in state:
        return out
    ver = state[ROWVER_KEY]
    newv = jnp.max(ver) + jnp.int32(1)
    out[ROWVER_KEY] = ver.at[safe_rows].set(newv, mode="drop")
    return out


def grad_row_bytes(grads, with_index: bool = True,
                   with_counts: bool = False) -> int:
    """Wire bytes per pushed row: the grad fields' widths at their dtypes,
    plus an int32 index in the sparse representation and an f32 counts
    column when a span family ships data counts.  One shared formula so
    every backend's ``wire_bytes`` counter measures the same thing."""
    total = 4 if with_index else 0
    for g in grads.values():
        g = jnp.asarray(g)
        total += int(np.dtype(g.dtype).itemsize) * int(g.shape[-1])
    if with_counts:
        total += 4
    return total


def quant_grad_row_bytes(grads, quant: str,
                         with_counts: bool = False) -> int:
    """Encoded wire bytes per pushed row under the ``sparse_q`` format:
    the int32 index survives, each grad field ships its values quantized
    — int8 (1 byte/element plus a 4-byte per-(row, field) scale bucket)
    or bf16 (2 bytes/element, no scale) — and the counts column, when a
    span family ships one, stays f32.  The sparse_q twin of
    :func:`grad_row_bytes`, used both by the crossover model and by the
    ledger's encoded-size booking."""
    if quant not in ("int8", "bf16"):
        raise ValueError(f"quant_grad_row_bytes: unknown quant {quant!r}")
    total = 4
    for g in grads.values():
        d = int(jnp.asarray(g).shape[-1])
        total += d + 4 if quant == "int8" else 2 * d
    if with_counts:
        total += 4
    return total


def quantize_dequantize(g, quant: str):
    """Round-trip one grad block through the ``sparse_q`` value encoding
    (what the receiver would reconstruct): ``int8`` scales each bucket
    (last axis) by max|g|/127 and rounds symmetrically; ``bf16`` is a
    dtype round-trip.  Always returns f32 — the quantization lives in
    the VALUES; downstream routing/apply is unchanged, which is what
    keeps the format decision bit-path-exact outside the documented
    envelope."""
    g = jnp.asarray(g, jnp.float32)
    if quant == "bf16":
        return g.astype(jnp.bfloat16).astype(jnp.float32)
    if quant != "int8":
        raise ValueError(f"quantize_dequantize: unknown quant {quant!r}")
    scale = jnp.max(jnp.abs(g), axis=-1, keepdims=True) * (1.0 / 127.0)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(g / safe), -127.0, 127.0)
    return q * jnp.where(scale > 0, scale, 0.0)


# -- numerics health tap (obs/numerics.py, ISSUE 13) ------------------------
# When the numerics plane is armed it installs a tap here; every
# EF/quantize path (ef_quantize_window for xla/tpu/hybrid, the local
# oracle's numpy twin) books its pre-vs-post quantization error
# sum-of-squares through it.  None (the default) traces NOTHING extra,
# which is what keeps `[obs] numerics: off` bit-identical — callers
# must rebuild/retrace their jitted steps when arming or clearing.
_NUMERICS_TAP = None


def set_numerics_tap(fn) -> None:
    global _NUMERICS_TAP
    _NUMERICS_TAP = fn


def clear_numerics_tap() -> None:
    set_numerics_tap(None)


def numerics_quant_err(err_sq) -> None:
    """Book one quantized window's error sum-of-squares (traced tracer
    or eager scalar) into the armed numerics tap; no-op when off."""
    tap = _NUMERICS_TAP
    if tap is not None:
        tap(err_sq)


def ef_quantize_window(state, ded_slots, ded_grads, capacity: int,
                       quant: str, trace_backend: Optional[str] = None):
    """Error-feedback quantize of one deduped window: drain each touched
    slot's residual into its gradient sum, quantize-dequantize, and
    store the new per-slot quantization error back into the ``<f>@ef``
    residual planes.  Returns ``(state', grads')`` with the residual
    planes replaced and the grads dequantized (f32, ready for the
    unchanged routing/apply path).  Fields without an ``@ef`` plane in
    ``state`` pass through untouched.

    Written to be correct under the tpu backend's DEVICE-LOCAL dedup,
    where the same slot can survive as owner in several devices' batch
    slices: the residual is drained into the globally FIRST occurrence
    only (representative trick over the full flattened batch), and the
    write-back is clear-then-scatter-ADD, which commutes under
    duplicates — the EF identity sum(applied_deq) + residual' ==
    sum(true grads) + residual holds exactly per slot either way.
    Plain traced jnp ops on the global arrays (GSPMD routes them), so
    the same code serves the xla oracle and the tpu/hybrid windows."""
    from swiftmpi_tpu.parameter.sparse_table import ef_name

    ded_slots = jnp.asarray(ded_slots, jnp.int32)
    B = ded_slots.shape[0]
    valid = ded_slots >= 0
    pos = jnp.arange(B, dtype=jnp.int32)
    safe = jnp.where(valid, ded_slots, capacity)
    rep = jnp.full((capacity + 1,), B, jnp.int32).at[safe].min(
        jnp.where(valid, pos, B), mode="drop")
    first = valid & (jnp.take(rep, safe) == pos)
    touched = jnp.zeros((capacity,), jnp.bool_).at[safe].set(
        True, mode="drop")
    gather_idx = jnp.clip(safe, 0, capacity - 1)
    out_state = dict(state)
    out_grads = dict(ded_grads)
    err_sq = None
    # tracer armed at trace time adds two |.|-sum reads per EF field —
    # pure reads, values untouched; same rebuild-to-arm contract as the
    # numerics tap above
    tracer = obs.get_tracer()
    drained = rebanked = None
    for f, g in ded_grads.items():
        efk = ef_name(f)
        if efk not in state:
            continue
        ef = state[efk]
        g = jnp.asarray(g, jnp.float32)
        res = jnp.take(ef, gather_idx, axis=0) * first[:, None]
        tot = g + res
        deq = quantize_dequantize(tot, quant) * valid[:, None]
        err = (tot - deq) * valid[:, None]
        cleared = ef * (~touched)[:, None]
        out_state[efk] = cleared.at[safe].add(err, mode="drop")
        out_grads[f] = deq
        if _NUMERICS_TAP is not None:
            fsq = jnp.sum(err ** 2)
            err_sq = fsq if err_sq is None else err_sq + fsq
        if tracer is not None:
            dsum = jnp.sum(jnp.abs(res))
            esum = jnp.sum(jnp.abs(err))
            drained = dsum if drained is None else drained + dsum
            rebanked = esum if rebanked is None else rebanked + esum
    if err_sq is not None:
        numerics_quant_err(err_sq)
    if tracer is not None and drained is not None:
        from functools import partial
        cb = partial(tracer.stage_ef, trace_backend or "?")
        if isinstance(drained, jax.core.Tracer):
            jax.debug.callback(cb, drained, rebanked)
        else:
            cb(float(drained), float(rebanked))
    return out_state, out_grads


def pull_row_bytes(state, fields) -> int:
    """Wire bytes per pulled row: int32 request index plus the pulled
    fields' widths at the table's stored dtypes.  The pull-side twin of
    :func:`grad_row_bytes` so ``pull_bytes`` means the same thing on
    every backend."""
    total = 4
    for f in fields:
        arr = state[f]
        total += int(np.dtype(arr.dtype).itemsize) * int(arr.shape[-1])
    return total


def quant_pull_row_bytes(state, fields, quant: str) -> int:
    """Encoded wire bytes per pulled row under the quantized pull
    formats: the int32 request index survives, each field ships its
    values int8 (1 byte/element plus a 4-byte per-(row, field) scale —
    the PR-10 delta codec's scheme, transfer/delta.py) or bf16 (2
    bytes/element, no scale).  The pull-side twin of
    :func:`quant_grad_row_bytes`, used by the pull pricer
    (transfer/plan.price_pull_formats) and the ledger's encoded
    booking — note a 1-wide int8 field prices at 4+1+4 = 9 > 8 bytes
    and correctly loses to ``full_f32``."""
    if quant not in ("int8", "bf16"):
        raise ValueError(f"quant_pull_row_bytes: unknown quant {quant!r}")
    total = 4
    for f in fields:
        d = int(state[f].shape[-1])
        total += d + 4 if quant == "int8" else 2 * d
    return total


@jax.tree_util.register_pytree_node_class
class PushSpec:
    """One gradient-family push: ``(slots, grads, mean)``.

    A pytree whose ``mean`` flag is static aux data, so a jitted
    function taking pushes as an argument (e.g. the async snapshot
    mode's ``jit(apply_fn)(state, pushes)``) sees a concrete Python
    bool, not a traced scalar.  Iterates like the plain 3-tuple it
    replaces.

    ``counts`` (non-None) marks a POSITION-INDEXED span family (the
    stencil w2v rendering): each row already carries the sum of its
    window-overlap contributions and ``counts[i]`` says how many, so
    ``mean`` normalization needs the data counts rather than
    1-per-row, and the apply step routes through ``push_span``: the
    push with that multiplicity."""

    def __init__(self, slots, grads, mean: bool = False, counts=None):
        self.slots = slots
        self.grads = grads
        self.mean = bool(mean)
        self.counts = counts

    def __iter__(self):
        return iter((self.slots, self.grads, self.mean))

    def tree_flatten(self):
        return (self.slots, self.grads, self.counts), self.mean

    @classmethod
    def tree_unflatten(cls, mean, children):
        return cls(children[0], children[1], mean, children[2])


class Transfer:
    """Backend interface: pure device-level pull/push."""

    name: str = "?"

    # -- wire traffic ledger (shared by every backend) ---------------------
    # ``wire_bytes`` counts push-side exchange PAYLOAD bytes (sparse:
    # valid rows x grad_row_bytes; dense: capacity x row bytes) and
    # ``dispatches`` the number of push-side exchanges — pulls are
    # ledgered separately (``pull_bytes``/``pull_rows``), so a window
    # that coalesces W pushes into one exchange shows a W-fold dispatch
    # drop regardless of the pull schedule.
    # Counting is off until ``count_traffic`` is set (one extra reduce
    # per push otherwise).  The counts are data-dependent under jit, so
    # the same tracer/eager discipline as the tpu backend's overflow
    # counter applies: traced values are staged via jax.debug.callback
    # (fires per compiled execution), eager device scalars queue and
    # materialize in :meth:`traffic`.

    def _wire_state(self) -> dict:
        st = self.__dict__.get("_wire_ledger")
        if st is None:
            st = self.__dict__["_wire_ledger"] = {
                "wire_bytes": 0, "dispatches": 0,
                "window_sparse": 0, "window_dense": 0,
                "window_fmt_dense": 0, "window_fmt_sparse": 0,
                "window_fmt_q": 0, "window_fmt_bitmap": 0,
                "window_fmt_sketch": 0,
                "collective_psum": 0, "collective_sparse_ar": 0,
                "hot_psum_bytes_saved": 0,
                "plan_compiles": 0, "plan_cache_hits": 0,
                "coalesced_rows_in": 0, "coalesced_rows_out": 0,
                "pull_bytes": 0, "pull_rows": 0, "pull_hot_rows": 0,
                "pull_cache_hits": 0, "pull_delta_rows": 0,
                "pull_bytes_saved": 0,
                "pull_fmt_full": 0, "pull_fmt_bf16": 0, "pull_fmt_q": 0,
                "pending": [], "pull_pending": [],
                "pull_hot_pending": []}
        return st

    #: decision string -> fine-grained format counter.  The legacy
    #: 2-way counters keep counting (dense -> window_dense, everything
    #: sparse-shaped -> window_sparse) so pre-4-way dashboards and
    #: goldens stay valid; the fmt counters record which format WON.
    _WINDOW_FMT_KEY = {"dense": "window_fmt_dense",
                       "sparse": "window_fmt_sparse",
                       "sparse_q": "window_fmt_q",
                       "bitmap": "window_fmt_bitmap",
                       "sparse_sketch": "window_fmt_sketch"}

    def _obs_inc(self, key: str, n, **labels) -> None:
        """Mirror a ledger increment into the telemetry registry as
        ``transfer/<key>{backend=<name>, **labels}``.  Telemetry off
        costs one branch; handles are cached per instance and re-fetched
        if the global registry was swapped (tests reset it)."""
        reg = obs.get_registry()
        if not reg.enabled:
            return
        cache = self.__dict__.get("_obs_cache")
        if cache is None or cache[0] is not reg:
            cache = self.__dict__["_obs_cache"] = (reg, {})
        ck = (key,) + tuple(sorted(labels.items())) if labels else key
        c = cache[1].get(ck)
        if c is None:
            # the one legit dynamic transfer/ name: TELEMETRY-CATALOG
            # validates `key` at every _obs_inc call site instead
            c = cache[1][ck] = reg.counter(  # smtpu-lint: disable=TELEMETRY-CATALOG
                "transfer/" + key, backend=self.name, **labels)
        c.inc(n)

    def _count_decision(self, st: dict, decision: str) -> None:
        """Book one window's wire-format decision: the legacy 2-way
        counter plus the 4-way ``window_fmt_*`` split, mirrored as a
        single fmt-labeled telemetry series
        ``transfer/window_fmt{backend=, fmt=}``."""
        legacy = "window_dense" if decision == "dense" else "window_sparse"
        st[legacy] += 1
        self._obs_inc(legacy, 1)
        fmt_key = self._WINDOW_FMT_KEY[decision]
        st[fmt_key] += 1
        self._obs_inc("window_fmt", 1,
                      fmt=fmt_key[len("window_fmt_"):])

    #: pull-format decision -> ledger counter (the pull family's
    #: sibling of ``_WINDOW_FMT_KEY``), mirrored as the fmt-labeled
    #: telemetry series ``transfer/pull_fmt{backend=, fmt=}``.
    _PULL_FMT_KEY = {"full_f32": "pull_fmt_full",
                     "bf16": "pull_fmt_bf16",
                     "sparse_q": "pull_fmt_q"}

    def _count_pull_decision(self, decision: str) -> None:
        """Book one pull's wire-format decision.  Host-side eager like
        :meth:`_count_collective` — the decision is plan-static per
        compiled pull program, so this fires once per ``pull`` CALL
        (trace time under jit), mirroring when the plan decision itself
        is made.  Only armed pulls reach here: with ``pull_quant`` and
        ``pull_cache`` both off the pull never compiles a plan and the
        ledger stays byte-for-byte the legacy one."""
        if not getattr(self, "count_traffic", False):
            return
        key = self._PULL_FMT_KEY[decision]
        self._wire_state()[key] += 1
        self._obs_inc("pull_fmt", 1, fmt=key[len("pull_fmt_"):])

    #: collective decision -> ledger counter (the dense/hot reconcile's
    #: sibling of ``_WINDOW_FMT_KEY``), mirrored as the kind-labeled
    #: telemetry series ``transfer/collective{backend=, kind=}``.
    _COLLECTIVE_KEY = {"psum": "collective_psum",
                       "psum_scatter": "collective_psum",
                       "sparse_allreduce": "collective_sparse_ar"}

    def _count_collective(self, collective: str) -> None:
        """Book one reconcile's collective decision.  Host-side eager —
        the decision is plan-static per compiled window program, and
        this fires once per push_window CALL (trace time under jit),
        mirroring when the plan decision itself is made."""
        if not getattr(self, "count_traffic", False):
            return
        key = self._COLLECTIVE_KEY[collective]
        self._wire_state()[key] += 1
        self._obs_inc("collective", 1, kind=key[len("collective_"):])

    def _accum_saved(self, nbytes) -> None:
        st = self._wire_state()
        st["hot_psum_bytes_saved"] += int(nbytes)
        self._obs_inc("hot_psum_bytes_saved", int(nbytes))

    def _record_saved(self, nbytes) -> None:
        """Record the wire bytes a sparse-allreduce reconcile saved over
        the dense collective it replaced (``dense model - booked``);
        traced values land via callback, same discipline as
        :meth:`_record_exchange`."""
        if not getattr(self, "count_traffic", False):
            return
        if isinstance(nbytes, jax.core.Tracer):
            jax.debug.callback(self._accum_saved, nbytes)
        else:
            self._accum_saved(nbytes)

    def _accum_wire(self, row_bytes, rows, ndisp: int = 1,
                    decision: Optional[str] = None,
                    base_bytes: int = 0) -> None:
        st = self._wire_state()
        nbytes = int(rows) * int(row_bytes) + int(base_bytes)
        st["wire_bytes"] += nbytes
        st["dispatches"] += ndisp
        self._obs_inc("wire_bytes", nbytes)
        self._obs_inc("dispatches", ndisp)
        if decision:
            self._count_decision(st, decision)
        # wire-tracing plane (obs/trace.py): the tracer reads the SAME
        # host landing point the ledger books through, so its records
        # agree with the counters by construction and arming it changes
        # nothing in the traced program
        tr = obs.get_tracer()
        if tr is not None:
            tr.on_exchange(self.name, int(rows), int(row_bytes),
                           base_bytes=int(base_bytes), decision=decision)

    def _record_exchange(self, rows, row_bytes: int,
                         decision: Optional[str] = None,
                         base_bytes: int = 0) -> None:
        """Record one push exchange of ``rows`` (traced or eager count)
        at ``row_bytes`` per row, plus ``base_bytes`` of per-exchange
        overhead independent of the row count (the bitmap format's
        capacity/8-byte occupancy mask)."""
        if not getattr(self, "count_traffic", False):
            return
        from functools import partial
        cb = partial(self._accum_wire, int(row_bytes), decision=decision,
                     base_bytes=int(base_bytes))
        if isinstance(rows, jax.core.Tracer):
            jax.debug.callback(cb, rows)
        elif obs.get_tracer() is not None:
            # armed tracer: land eagerly (program order) so the window
            # state machine attributes bytes to the RIGHT open record —
            # the batching queue would park this exchange past the next
            # window's open.  Ledger totals are identical either way.
            self._accum_wire(int(row_bytes), rows, decision=decision,
                             base_bytes=int(base_bytes))
        else:
            st = self._wire_state()
            st["pending"].append((int(row_bytes), rows, decision,
                                  int(base_bytes)))
            if len(st["pending"]) >= 1024:
                pending, st["pending"] = st["pending"], []
                for rb, r, d, bb in pending:
                    self._accum_wire(rb, r, decision=d, base_bytes=bb)

    def _accum_pull(self, row_bytes, rows) -> None:
        st = self._wire_state()
        nbytes = int(rows) * int(row_bytes)
        st["pull_bytes"] += nbytes
        st["pull_rows"] += int(rows)
        self._obs_inc("pull_bytes", nbytes)
        self._obs_inc("pull_rows", int(rows))

    def _record_pull(self, rows, row_bytes: int) -> None:
        """Record one pull exchange of ``rows`` (traced or eager count)
        at ``row_bytes`` per row.  ``row_bytes == 0`` still counts rows
        — the hybrid backend's hot hits are local replica reads that
        ship nothing but should show up in ``pull_rows`` so hit ratios
        can be derived from the ledger alone."""
        if not getattr(self, "count_traffic", False):
            return
        from functools import partial
        cb = partial(self._accum_pull, int(row_bytes))
        if isinstance(rows, jax.core.Tracer):
            jax.debug.callback(cb, rows)
        else:
            st = self._wire_state()
            st["pull_pending"].append((int(row_bytes), rows))
            if len(st["pull_pending"]) >= 1024:
                pending, st["pull_pending"] = st["pull_pending"], []
                for rb, r in pending:
                    self._accum_pull(rb, r)

    def _accum_pull_hot(self, rows) -> None:
        st = self._wire_state()
        st["pull_hot_rows"] += int(rows)
        self._obs_inc("pull_hot_rows", int(rows))

    def _record_pull_hot(self, rows) -> None:
        """Record ``rows`` pull hits answered by a local replica (the
        hybrid backend's hot head).  These rows are INCLUDED in
        ``pull_rows`` (so row totals stay comparable across backends)
        but ship zero wire bytes; this explicit series lets miss-ratio
        math separate replica hits from actually-shipped tail rows
        instead of inferring it from ``pull_bytes == 0`` rows."""
        if not getattr(self, "count_traffic", False):
            return
        if isinstance(rows, jax.core.Tracer):
            jax.debug.callback(self._accum_pull_hot, rows)
        else:
            st = self._wire_state()
            st["pull_hot_pending"].append(rows)
            if len(st["pull_hot_pending"]) >= 1024:
                pending, st["pull_hot_pending"] = \
                    st["pull_hot_pending"], []
                for r in pending:
                    self._accum_pull_hot(r)

    def _pull_shadow_get(self):
        """This worker's versioned :class:`~swiftmpi_tpu.transfer.
        pull_cache.PullCache` shadow, (re)built lazily when the
        ``pull_cache`` knob (line count) or the oracle mode moved.
        Host-side state — it never appears in a traced program, which
        is what keeps ``pull_cache`` a pure ledger/wire-model plane:
        a version-exact hit's cached row is bit-identical to the fresh
        gather, so device values need no splice."""
        from swiftmpi_tpu.transfer.pull_cache import PullCache
        sh = self.__dict__.get("_pull_shadow")
        lines = int(self.pull_cache)
        oracle = bool(self.pull_cache_oracle)
        if sh is None or sh.lines != lines or sh.store_rows != oracle:
            sh = self.__dict__["_pull_shadow"] = PullCache(
                lines, store_rows=oracle)
        return sh

    def pull_shadow_flush(self) -> None:
        """Drop every cached (slot, version) tag: the worker starts
        cold.  Called on membership changes and by the model's
        restore/resume path — a rewound table can re-issue version
        stamps, after which a warm cache could false-hit (the
        invalidation contract in transfer/pull_cache.py)."""
        sh = self.__dict__.get("_pull_shadow")
        if sh is not None:
            sh.flush()

    def _accum_pull_cached(self, val_bytes, full_row_bytes, capacity,
                           fields, slots, versions, *rows) -> None:
        """Host landing point for one watermarked pull execution: run
        the cache shadow over ``(slots, versions)`` and book the
        delta-pull wire model —

          request   8 bytes/valid row (int32 key + int32 watermark)
          response  ceil(valid/8) hit-bitmap bytes, plus the plan's
                    encoded value bytes per MISS row only

        against the ``full_row_bytes`` baseline the uncached wire
        would have booked; the difference lands on
        ``pull_bytes_saved``.  ``rows`` (oracle mode only) are the
        fresh field arrays the shadow value-checks hits against.
        Fires per compiled execution via ``jax.debug.callback`` —
        (slots, versions, rows) are gathered at one program point, so
        the shadow's stored (version, value) pairs are always mutually
        consistent even if the runtime reorders callbacks."""
        sh = self._pull_shadow_get()
        slots = np.asarray(slots).ravel()
        rowmap = dict(zip(fields, rows)) if rows else None
        hit = sh.lookup(slots, versions, int(capacity), rows=rowmap)
        n_valid = int((slots >= 0).sum())
        n_hit = int(hit.sum())
        n_miss = n_valid - n_hit
        booked = 8 * n_valid + (n_valid + 7) // 8 + n_miss * int(val_bytes)
        saved = max(0, n_valid * int(full_row_bytes) - booked)
        st = self._wire_state()
        st["pull_bytes"] += booked
        st["pull_rows"] += n_valid
        st["pull_cache_hits"] += n_hit
        st["pull_delta_rows"] += n_miss
        st["pull_bytes_saved"] += saved
        self._obs_inc("pull_bytes", booked)
        self._obs_inc("pull_rows", n_valid)
        self._obs_inc("pull_cache_hits", n_hit)
        self._obs_inc("pull_delta_rows", n_miss)
        self._obs_inc("pull_bytes_saved", saved)

    def _accum_coalesce(self, decision, rows_in, rows_out) -> None:
        st = self._wire_state()
        st["coalesced_rows_in"] += int(rows_in)
        st["coalesced_rows_out"] += int(rows_out)
        self._obs_inc("coalesced_rows_in", int(rows_in))
        self._obs_inc("coalesced_rows_out", int(rows_out))
        if decision:
            self._count_decision(st, decision)
            tr = obs.get_tracer()
            if tr is not None:
                # a decision-carrying dedup opens this backend's window
                # record; the following exchange callback seals it
                tr.on_window(self.name, decision, int(rows_in),
                             int(rows_out))

    def _record_coalesce(self, rows_in, rows_out,
                         decision: Optional[str] = None) -> None:
        """Record one window's pre-exchange dedup (rows before/after) and
        its wire-format decision; fires per compiled execution under an
        outer trace, same discipline as :meth:`_record_exchange`."""
        if not getattr(self, "count_traffic", False):
            return
        from functools import partial
        cb = partial(self._accum_coalesce, decision)
        if isinstance(rows_in, jax.core.Tracer) \
                or isinstance(rows_out, jax.core.Tracer):
            jax.debug.callback(cb, rows_in, rows_out)
        else:
            self._accum_coalesce(decision, rows_in, rows_out)

    def wire_traffic(self) -> Dict[str, int]:
        """Cumulative wire ledger (flushes traced callbacks and queued
        eager scalars): ``wire_bytes``, ``dispatches``, the window
        path's ``window_sparse``/``window_dense`` decision counts plus
        ``coalesced_rows_in``/``coalesced_rows_out`` (rows before/after
        the per-window dedup), and the pull side's
        ``pull_bytes``/``pull_rows``.

        Reset semantics (contract for all backends, enforced by
        tests/test_telemetry.py): every value is a **monotonically
        non-decreasing total** over the Transfer instance's lifetime.
        There is no reset method on purpose — a reader wanting
        per-interval numbers snapshots twice and subtracts (exactly what
        the telemetry StepRecorder does with the registry mirror of
        these counters).  Calling this method never perturbs the
        ledger."""
        jax.effects_barrier()
        st = self._wire_state()
        pending, st["pending"] = st["pending"], []
        for rb, r, d, bb in pending:
            self._accum_wire(rb, r, decision=d, base_bytes=bb)
        pulls, st["pull_pending"] = st["pull_pending"], []
        for rb, r in pulls:
            self._accum_pull(rb, r)
        hots, st["pull_hot_pending"] = st["pull_hot_pending"], []
        for r in hots:
            self._accum_pull_hot(r)
        return {k: v for k, v in st.items()
                if k not in ("pending", "pull_pending",
                             "pull_hot_pending")}

    def traffic(self) -> Dict[str, int]:
        """Cumulative traffic counters; every backend reports at least
        the wire ledger so cross-backend goldens compare like with
        like.  Backends with routed/hot paths extend this dict.

        Same contract as :meth:`wire_traffic`: monotonic totals, no
        reset, deltas are the caller's job.  The identical numbers are
        mirrored live into the telemetry registry as
        ``transfer/<key>{backend=<name>}`` counters (when telemetry is
        on), so per-step deltas come from ``telemetry.jsonl`` without
        ever calling this (and without its ``jax.effects_barrier``)."""
        return self.wire_traffic()

    def traffic_delta(self, since: Optional[Dict[str, int]] = None
                      ) -> Dict[str, int]:
        """Per-interval traffic: :meth:`traffic` minus an earlier
        snapshot ``since`` (itself a ``traffic()`` return value).

        This is the helper side of the monotonic-totals contract: the
        ledger never resets, so interval numbers are always
        snapshot-and-subtract — done HERE once instead of hand-rolled
        at every call site.  ``since=None`` (or a key absent from
        ``since``, e.g. a snapshot taken before a counter existed)
        subtracts zero, so the result degrades to the totals."""
        cur = self.traffic()
        if not since:
            return cur
        return {k: v - since.get(k, 0) for k, v in cur.items()}

    # -- elastic membership (ISSUE 16) -------------------------------------
    #: last adopted membership epoch; -1 = never told (static world).
    #: Class-level DEFAULTS — the guarded mutation path is
    #: :meth:`on_membership` only.
    _membership_epoch = -1
    _live_ranks: Optional[Tuple[int, ...]] = None

    def on_membership(self, epoch: int, live_ranks) -> None:
        """Adopt an elastic membership change (cluster/membership.py):
        the world's live-rank set or shard ownership moved, so anything
        this backend compiled or estimated against the old world shape
        is suspect.  Raises
        :class:`~swiftmpi_tpu.cluster.membership.StaleEpochError` if
        ``epoch`` regresses below what was already adopted (acting on a
        stale world view is the split-brain the epoch protocol
        prevents); adopting the SAME epoch again is a no-op, so every
        component in a process can be told independently.  Backends
        override :meth:`_membership_changed` to invalidate their
        compiled caches — the base books the epoch and mirrors the
        change into telemetry."""
        from swiftmpi_tpu.cluster.membership import StaleEpochError
        epoch = int(epoch)
        if epoch < self._membership_epoch:
            raise StaleEpochError(
                f"{self.name}: membership epoch {epoch} regressed "
                f"below adopted {self._membership_epoch}")
        if epoch == self._membership_epoch:
            return
        # epoch-guard: regression raised StaleEpochError above — the
        # membership state below only ever moves forward
        self._membership_epoch = epoch
        self._live_ranks = tuple(int(r) for r in live_ranks)
        self._obs_inc("membership_changes", 1)
        # shard ownership moved: cached (slot, version) tags describe
        # rows that may now live elsewhere — start cold
        self.pull_shadow_flush()
        self._membership_changed()

    def _membership_changed(self) -> None:
        """Backend hook, called once per adopted epoch: drop whatever
        was specialized to the old world shape.  Default: nothing (a
        backend with no world-shaped state)."""

    # -- wire-format decision hook ----------------------------------------
    #: post-dedup unique-row estimate for the window crossover (set by
    #: the model from the vocab histogram; retuned online by the
    #: control plane).  None = use the raw pre-dedup row count.
    window_expected_unique = None

    #: value quantization for the window push's sparse formats:
    #: ``"off"`` (default — 2-way decision, bit-identical to the
    #: pre-quantization wire) | ``"int8"`` | ``"bf16"``.  Set from
    #: ``[cluster] wire_quant`` by the model, which also arms the
    #: ``@ef`` residual planes; flipping it mid-run requires a step
    #: rebuild (the decision is baked at trace time).
    wire_quant = "off"

    #: safety factor pricing the lossy rung: ``sparse_q`` wins only
    #: when its volume times this still beats the best lossless format
    #: (key_index.window_wire_format).  Raise toward 2.0 to keep
    #: quantization off marginal windows, lower toward 1.0 to compress
    #: aggressively.  Host-side like the dense ratio — takes effect on
    #: the next decision.
    wire_quant_guard = 1.25

    #: value quantization for the pull wire (``transfer.plan.
    #: PULL_QUANT_MODES``): ``"off"`` (default — pulls ship ``full_f32``
    #: and stay bit-identical to the legacy wire) | ``"int8"`` (the
    #: ``sparse_q`` rung, PR-10 codec scheme) | ``"bf16"``.  Set from
    #: ``[cluster] pull_quant``.  Quantized pulls perturb the FORWARD
    #: READ, not the server state, so parity holds to the PR-10
    #: trajectory envelope rather than bit-exactness.
    pull_quant = "off"

    #: safety factor pricing the encoded pull rungs: an encoded format
    #: wins only when its volume times this still beats ``full_f32``
    #: (transfer.plan.price_pull_formats).  Same semantics and default
    #: as ``wire_quant_guard``.
    pull_quant_guard = 1.25

    #: versioned pull-cache size in LINES (direct-mapped,
    #: transfer/pull_cache.py); 0 = off.  Set from ``[cluster]
    #: pull_cache``.  Arming requires the table's row-version plane
    #: (``SparseTable.ensure_row_versions``) — the model arms both
    #: together.  The cache is a host-side wire-model shadow: values
    #: are unchanged by construction, only the pull ledger moves.
    pull_cache = 0

    #: test-only oracle mode: the shadow stores actual row values and
    #: asserts cached == fresh on every version-exact hit — proving
    #: every apply path bumps its rows' versions.
    pull_cache_oracle = False

    #: arm the ``sparse_sketch`` wire rung (transfer/sketch.py):
    #: counting-sketch index compression between the ``bitmap`` and
    #: ``sparse`` rungs.  Lossless, so unlike ``wire_quant`` it needs no
    #: EF planes — but with both knobs off the decision stays the exact
    #: legacy 2-way (bit-identity guarantee), so arming requires the
    #: usual step rebuild.  Set from ``[cluster] wire_sketch``.
    wire_sketch = False

    #: collective selection mode for the dense/hot reconcile planes
    #: (``transfer.plan.COLLECTIVE_MODES``): ``"psum"`` (default — the
    #: legacy dense collective, bit-identical to the pre-PR wire),
    #: ``"sparse_allreduce"`` (pin the Ok-Topk split-and-exchange), or
    #: ``"auto"`` (price by touched-fraction crossover,
    #: key_index.price_hot_collectives).  Set from ``[cluster]
    #: collective``; flipping it mid-run requires a step rebuild (the
    #: collective is baked into the compiled reconcile).
    collective_mode = "psum"

    #: live hot-touch density signal for the ``auto`` crossover:
    #: expected fraction of the hot/dense capacity touched per window.
    #: Seeded by the model from the vocab histogram; retuned online by
    #: the Controller from the DecayedSketch's hot-touch counts.
    #: ``None`` = unknown → ``auto`` conservatively keeps the dense
    #: collective.
    hot_touched_fraction = None

    #: SparCML-style safety factor on the sparse collective: the dense
    #: collective wins while ``sparse_bytes * ratio >= dense_bytes``
    #: (sparse must beat dense by this margin to pay for its irregular
    #: index stream).  Host-side like wire_dense_ratio — takes effect
    #: on the next plan compile.
    sparse_ar_ratio = 2.0

    def _ratio_state(self) -> dict:
        st = self.__dict__.get("_wire_ratios")
        if st is None:
            st = self.__dict__["_wire_ratios"] = {}
        return st

    def wire_dense_ratio(self, family: Optional[str] = None) -> float:
        """Current sparse/dense crossover ratio for a push family
        (``None`` = the default family): dense wins when
        ``sparse_volume * ratio >= dense_volume``.  2.0 is the
        SparCML-derived seed default (see key_index.window_wire_format);
        the control plane retunes it per family at runtime."""
        st = self._ratio_state()
        return float(st.get(family, st.get(None, 2.0)))

    def set_wire_dense_ratio(self, ratio: float,
                             family: Optional[str] = None) -> None:
        """Set the crossover ratio (per ``family``, or the default when
        ``family=None``).  Takes effect on the NEXT decision — decisions
        are made host-side per call, so no recompile is needed."""
        self._ratio_state()[family] = float(ratio)

    def _window_plan(self, rows: int, capacity: int, row_bytes: int,
                     quant_row_bytes: Optional[int] = None,
                     family: Optional[str] = "window",
                     with_counts: bool = True):
        """Compile (or fetch) this instance's :class:`TrafficPlan` for
        one window shape (transfer/plan.py) and fire the plan's
        observation side-channels: compile/hit counters on the wire
        ledger, and — armed — the full candidate pricing on the wire
        tracer, so each runtime window record can say WHY its format
        won (obs/trace.py).  The on_decision tap fires per CALL, not
        per compile: trace streams see every window, cached or not."""
        from swiftmpi_tpu.transfer.plan import compile_window_plan
        plan, hit = compile_window_plan(
            self, int(rows), int(capacity), int(row_bytes),
            quant_row_bytes, with_counts, family=family)
        if getattr(self, "count_traffic", False):
            key = "plan_cache_hits" if hit else "plan_compiles"
            self._wire_state()[key] += 1
            self._obs_inc(key, 1)
        tr = obs.get_tracer()
        if tr is not None:
            tr.on_decision(self.name, plan.wire_format, plan.prices,
                           plan.rows, plan.capacity, plan.row_bytes,
                           quant=plan.quant)
        return plan

    def _pull_plan(self, rows: int, capacity: int, row_bytes: int,
                   quant_row_bytes: Optional[int] = None):
        """Compile (or fetch) this instance's :class:`PullPlan`
        (transfer/plan.py's ``compile_pull_plan``) — the pull sibling
        of :meth:`_window_plan`, with the same observation discipline:
        compile/hit counters on the wire ledger, the format decision
        on the ``pull_fmt`` counters, and the pricing evidence on the
        armed wire tracer (decision key ``pull_<format>`` so pulls
        don't collide with the window formats in the trace price
        cache)."""
        from swiftmpi_tpu.transfer.plan import compile_pull_plan
        plan, hit = compile_pull_plan(self, int(rows), int(capacity),
                                      int(row_bytes), quant_row_bytes)
        if getattr(self, "count_traffic", False):
            key = "plan_cache_hits" if hit else "plan_compiles"
            self._wire_state()[key] += 1
            self._obs_inc(key, 1)
        self._count_pull_decision(plan.wire_format)
        tr = obs.get_tracer()
        if tr is not None:
            tr.on_decision(self.name, "pull_" + plan.wire_format,
                           plan.prices, plan.rows, plan.capacity,
                           plan.row_bytes, quant=plan.quant)
        return plan

    def _hot_plan(self, n_hot: int, width_bytes: int):
        """Compile (or fetch) the hot-plane reconcile's
        :class:`TrafficPlan` (transfer/plan.py's ``compile_hot_plan``) —
        the hot sibling of :meth:`_window_plan`, with the same
        observation discipline: compile/hit counters on the wire ledger,
        and the collective's pricing evidence on the armed wire tracer
        (decision key ``hot_<collective>`` so hot rows don't collide
        with the window formats in the trace price cache)."""
        from swiftmpi_tpu.transfer.plan import compile_hot_plan
        plan, hit = compile_hot_plan(self, int(n_hot), int(width_bytes))
        if getattr(self, "count_traffic", False):
            key = "plan_cache_hits" if hit else "plan_compiles"
            self._wire_state()[key] += 1
            self._obs_inc(key, 1)
        tr = obs.get_tracer()
        if tr is not None:
            tr.on_decision(self.name, "hot_" + plan.collective,
                           plan.prices, plan.rows, plan.capacity,
                           plan.row_bytes)
        return plan

    def decide_wire_format(self, rows: int, capacity: int,
                           row_bytes: int,
                           family: Optional[str] = None,
                           quant_row_bytes: Optional[int] = None) -> str:
        """``"sparse" | "dense"`` — or, with ``wire_quant`` /
        ``wire_sketch`` armed and a ``quant_row_bytes`` estimate
        supplied, the full 5-way ``"sparse" | "dense" | "bitmap" |
        "sparse_q" | "sparse_sketch"`` — for one exchange of ``rows``
        candidate rows against a ``capacity``-row dense alternative.
        The ONE place the wire-format question is asked — call sites no
        longer read config/module constants directly, so the control
        plane can steer the crossover (ratio and expected-unique
        estimate) without touching compiled code.

        Thin shim over :meth:`_window_plan`: the pricing, caching and
        trace taps all live in the TrafficPlan compiler now; this keeps
        the historical ask-for-a-string entry point for the control
        plane and the calibration tools."""
        return self._window_plan(rows, capacity, row_bytes,
                                 quant_row_bytes=quant_row_bytes,
                                 family=family).wire_format

    def _trace_keys(self, ded_slots, cap_per_shard: Optional[int] = None,
                    n_shards: Optional[int] = None) -> None:
        """Ship a bounded strided reservoir of the surviving (deduped,
        ``-1``-padded) slot array — and, when the backend knows its
        ``slot // cap_per_shard`` owner mapping, the surviving-row count
        per destination shard — to the armed wire tracer.  Pure reads
        plus one host callback, added to the traced program only when a
        tracer with a key reservoir is installed at trace time (values
        are untouched, so trajectories stay bit-identical either way;
        arming mid-run requires the usual step rebuild)."""
        tr = obs.get_tracer()
        if tr is None or tr.keys <= 0:
            return
        from functools import partial
        ded_slots = jnp.asarray(ded_slots)
        B = int(ded_slots.shape[0])
        if B == 0:
            return
        stride = max(B // int(tr.keys), 1)
        sample = ded_slots[::stride][:int(tr.keys)]
        cb = partial(tr.stage_keys, self.name)
        shard_rows = None
        if cap_per_shard and n_shards:
            valid = ded_slots >= 0
            own = jnp.where(valid,
                            ded_slots // jnp.int32(cap_per_shard),
                            jnp.int32(n_shards))
            shard_rows = jnp.zeros((int(n_shards) + 1,), jnp.int32).at[
                own].add(1, mode="drop")[:int(n_shards)]
        if isinstance(sample, jax.core.Tracer) or \
                isinstance(shard_rows, jax.core.Tracer):
            if shard_rows is None:
                jax.debug.callback(cb, sample)
            else:
                jax.debug.callback(cb, sample, shard_rows)
        elif shard_rows is None:
            cb(np.asarray(sample))
        else:
            cb(np.asarray(sample), np.asarray(shard_rows))

    def pull(self, state: TableState, slots, access: AccessMethod,
             fields=None) -> TableState:
        """Gather rows for ``slots``.  ``fields`` restricts the pull to a
        subset of ``access.pull_fields`` — a caller whose slot groups
        need different fields (w2v: h for targets, v for contexts)
        splits its pulls rather than gathering every field for every
        slot and discarding half the bytes.

        This method is THE pull-family TrafficPlan interpreter (the
        single dispatch point the PLAN-DISPATCH lint rule pins, the
        pull sibling of :meth:`push_window`): it compiles a
        :class:`PullPlan` (transfer/plan.py) when the ``pull_quant`` /
        ``pull_cache`` knobs are armed and executes it over the
        backend's ONE structural primitive — :meth:`_prim_pull`, a
        plain masked row gather — with every ledger/cache/quant tap
        fired from HERE.  Backends never ask the pull-format question
        and never book the pull ledger.  With both knobs off the pull
        books and gathers exactly the legacy wire — bit-identical by
        construction."""
        from swiftmpi_tpu.transfer.plan import pull_route
        fields = tuple(fields or access.pull_fields)
        route = pull_route(self.name)
        # the one dispatch point, so every backend's gather (and whatever
        # XLA attaches to it) is booked under the `pull` phase
        with obs.named_scope("pull"):
            if route.placement == "hot_split":
                return self._interpret_pull_hot_split(state, slots, access,
                                                      fields)
            return self._interpret_pull_flat(state, slots, fields)

    def _prim_pull(self, state: TableState, slots, fields) -> TableState:
        """Backend pull primitive: masked row gather of ``fields`` at
        ``slots`` (``-1`` padding yields zero rows), NO ledger booking
        and no format logic — the interpreter owns both.  Structural
        routing accounting (the tpu backend's routed-row and overflow
        counters) stays with the primitive, like the push executors'."""
        raise NotImplementedError

    def _interpret_pull_flat(self, state: TableState, slots,
                             fields) -> TableState:
        """Execute one pull on a ``flat`` route.  Armed, the plan's
        format prices the wire (encoded rungs round-trip the pulled
        values through :func:`quantize_dequantize` — the forward read
        perturbs, the server state does not) and the versioned cache
        shadow books the delta wire: the row-version plane rides the
        SAME routed gather as the value rows (the watermark protocol's
        4 bytes/row), then lands host-side via the ledger's callback
        discipline."""
        from swiftmpi_tpu.parameter.sparse_table import ROWVER_KEY
        from swiftmpi_tpu.transfer.plan import pull_route
        route = pull_route(self.name)
        capacity = next(iter(state.values())).shape[0]
        row_bytes = pull_row_bytes(state, fields)
        quant = self.pull_quant
        armed = quant != "off" or bool(self.pull_cache)
        if route.eager:
            slots_h = np.asarray(slots, np.int64)
            n_valid = int((slots_h >= 0).sum())
            if not armed:
                self._record_pull(n_valid, row_bytes)
                return self._prim_pull(state, slots, fields)
            qrb = (quant_pull_row_bytes(state, fields, quant)
                   if quant != "off" else None)
            plan = self._pull_plan(int(slots_h.size), capacity,
                                   row_bytes, qrb)
            cached = plan.cached and ROWVER_KEY in state
            if cached:
                out = self._prim_pull(state, slots,
                                      fields + (ROWVER_KEY,))
                vers = np.asarray(out.pop(ROWVER_KEY)).ravel()
                if self.count_traffic:
                    rows = (tuple(np.asarray(out[f]) for f in fields)
                            if self.pull_cache_oracle else ())
                    self._accum_pull_cached(
                        plan.wire_row_bytes - 4, row_bytes, capacity,
                        fields, slots_h.ravel(), vers, *rows)
            else:
                out = self._prim_pull(state, slots, fields)
                self._record_pull(n_valid, plan.wire_row_bytes)
            if plan.wire_format != "full_f32":
                for f in fields:
                    out[f] = np.asarray(
                        quantize_dequantize(out[f], plan.quant))
            return out
        slots_j = jnp.asarray(slots, jnp.int32)
        if not armed:
            self._record_pull(jnp.sum(slots_j >= 0), row_bytes)
            return self._prim_pull(state, slots_j, fields)
        qrb = (quant_pull_row_bytes(state, fields, quant)
               if quant != "off" else None)
        plan = self._pull_plan(int(slots_j.size), capacity, row_bytes,
                               qrb)
        cached = plan.cached and ROWVER_KEY in state
        if cached:
            out = self._prim_pull(state, slots_j,
                                  fields + (ROWVER_KEY,))
            vers = out.pop(ROWVER_KEY)
            if self.count_traffic:
                from functools import partial
                cb = partial(self._accum_pull_cached,
                             plan.wire_row_bytes - 4, row_bytes,
                             capacity, fields)
                rows = (tuple(out[f] for f in fields)
                        if self.pull_cache_oracle else ())
                if isinstance(slots_j, jax.core.Tracer) \
                        or isinstance(vers, jax.core.Tracer):
                    jax.debug.callback(cb, slots_j, vers, *rows)
                else:
                    cb(np.asarray(slots_j), np.asarray(vers),
                       *(np.asarray(r) for r in rows))
        else:
            out = self._prim_pull(state, slots_j, fields)
            self._record_pull(jnp.sum(slots_j >= 0),
                              plan.wire_row_bytes)
        if plan.wire_format != "full_f32":
            out = {f: quantize_dequantize(out[f], plan.quant)
                   for f in fields}
        return out

    def _interpret_pull_hot_split(self, state: TableState, slots, access,
                                  fields) -> TableState:
        """Execute the ``hot_split`` pull placement (hybrid): replica
        hits resolved from the local hot head at 0 bytes exactly as the
        legacy wire books them, tail rows re-based by ``-n_hot`` and
        re-interpreted through the tail backend's ``pull`` — so the
        tail's cache/quant/ledger compose exactly as they do
        standalone, and hot reads are never quantized (the replica is
        reconciled losslessly by the hot psum).  Uses the hybrid
        backend's structural primitives (``_pad_batch``,
        ``_split_state``, ``_n_hot``) — only reachable on routes
        declaring ``placement="hot_split"``."""
        slots = jnp.asarray(slots, jnp.int32)
        slots, _, _, B = self._pad_batch(slots)
        tail_state, hot_state = self._split_state(state)
        n_hot = self._n_hot(state)
        if n_hot == 0:
            out = self.tail.pull(tail_state, slots, access,
                                 fields=fields)
            return {f: v[:B] for f, v in out.items()}
        is_hot = (slots >= 0) & (slots < n_hot)
        tail_slots = jnp.where(slots >= n_hot, slots - n_hot, -1)
        out = self.tail.pull(tail_state, tail_slots, access,
                             fields=fields)
        n_hot_rows = jnp.sum(is_hot)
        if self.count_traffic:
            # replica hits ship nothing: rows counted, zero bytes —
            # the 0-byte hot booking the cross-backend goldens pin
            self._record_hot(n_hot_rows, 0)
            self._record_pull(n_hot_rows, 0)
            self._record_pull_hot(n_hot_rows)
        safe_hot = jnp.clip(slots, 0, n_hot - 1)
        return {
            f: jnp.where(is_hot[..., None],
                         jnp.take(hot_state[f], safe_hot, axis=0),
                         out[f])[:B]
            for f in fields}

    def push(self, state: TableState, slots, grads: TableState,
             access: AccessMethod, mean: bool = False) -> TableState:
        """Apply ``grads`` at ``slots``.  ``mean=True`` divides each
        unique key's gradient sum by its contribution count before the
        access rule runs — the reference's ``grad /= count``
        normalization at push serialization (word2vec.h:120-132,
        lr.cpp:32-38), folded into the backend's own dedup pass.  Doing
        it here instead of pre-scaling each contribution saves a
        capacity-sized scatter + a batch-sized gather + a (B, d)
        multiply per push on the worker side (measured at ~25% of the
        w2v step, docs/ARCHITECTURE.md), and matches the reference's
        sum-then-divide order of operations bit-for-bit."""
        raise NotImplementedError

    def push_window(self, state: TableState, slots, grads: TableState,
                    access: AccessMethod, mean: bool = False,
                    counts=None) -> TableState:
        """Window-coalesced push: ``slots`` is ``(W, B)``, ``grads``
        ``{f: (W, B, d)}``, ``counts`` (optional) ``(W, B)`` — W steps'
        pushes accumulated into one buffer and exchanged ONCE.

        Semantics are push's sum-then-apply-once rule extended across
        the window: every (step, position) contribution to a key is
        summed, ``mean=True`` divides by the TOTAL window contribution
        count, and the access rule runs once per unique row per window.
        At ``W == 1`` this is the flatten of a unit axis followed by the
        per-step ``push``/``push_span`` — bit-identical to the per-step
        path by construction, so every existing parity oracle applies.
        At ``W > 1`` the update differs from W sequential applies by the
        optimizer's window staleness (bounded by W-1 steps; envelope
        documented in docs/ARCHITECTURE.md "Window-coalesced push").

        This method is THE TrafficPlan interpreter (the single dispatch
        point the PLAN-DISPATCH lint rule pins): it compiles a plan
        (transfer/plan.py) per window family and executes it over the
        backend's primitives — ``_prim_window_dedup``, ``_prim_ef_drain``,
        ``_prim_window_exchange``, ``_push_window_dense`` — with every
        obs/trace/numerics tap fired from HERE.  Backends never ask the
        wire-format question and never branch on a format name.  W == 1
        windows (and local/xla windows with every compression knob off)
        take :meth:`_push_window_passthrough` untouched — bit-identical
        to the pre-plan wire by construction."""
        from swiftmpi_tpu.transfer.plan import window_route
        route = window_route(self.name)
        if route.eager:
            shaped = np.asarray(slots, np.int64)
        else:
            shaped = slots = jnp.asarray(slots, jnp.int32)
        if shaped.ndim < 2 or shaped.shape[0] == 1:
            return self._push_window_passthrough(state, slots, grads,
                                                 access, mean=mean,
                                                 counts=counts)
        armed = self.wire_quant != "off" or bool(self.wire_sketch)
        if not route.always_decide and not armed:
            return self._push_window_passthrough(state, slots, grads,
                                                 access, mean=mean,
                                                 counts=counts)
        # flatten the (W, B) window ONCE, in the route's element space
        if route.eager:
            flat = shaped.reshape(-1)
            fgrads = {}
            for f, g in grads.items():
                g = np.asarray(g, np.float32)
                fgrads[f] = g.reshape((-1,) + g.shape[2:])
            fcounts = None if counts is None else np.asarray(
                counts, np.float32).reshape(-1)
        else:
            flat = shaped.reshape(-1)
            fgrads = {f: jnp.asarray(g).reshape(
                (-1,) + jnp.asarray(g).shape[2:])
                for f, g in grads.items()}
            fcounts = None if counts is None else jnp.asarray(
                counts, jnp.float32).reshape(-1)
        if not route.counts_follow_data and fcounts is None:
            # oracle routes price and ship with_counts rows regardless
            # (legacy local/xla behavior, kept bit-identical)
            fcounts = (np.ones(flat.shape, np.float32) if route.eager
                       else jnp.ones(flat.shape, jnp.float32))
        if route.placement == "hot_split":
            return self._interpret_window_hot_split(
                state, flat, fgrads, fcounts, access, mean,
                counts_present=counts is not None)
        return self._interpret_window_flat(
            state, flat, fgrads, access, mean, fcounts,
            passthrough=(slots, grads, counts))

    def _push_window_passthrough(self, state: TableState, slots, grads,
                                 access: AccessMethod, mean: bool = False,
                                 counts=None) -> TableState:
        """The no-plan window executor: flatten and delegate to the
        per-step ``push``/``push_span``.  Taken for W == 1 windows on
        every backend and for whole W > 1 windows on the non-
        ``always_decide`` routes with all compression knobs off — the
        paths whose bit-identity the parity goldens pin."""
        slots = jnp.asarray(slots)
        flat = slots.reshape(-1)
        fgrads = {f: jnp.asarray(g).reshape((-1,) + jnp.asarray(g).shape[2:])
                  for f, g in grads.items()}
        if counts is not None:
            return self.push_span(state, flat, fgrads,
                                  jnp.asarray(counts).reshape(-1),
                                  access, mean=mean)
        return self.push(state, flat, fgrads, access, mean=mean)

    def _trace_shard_args(self, capacity: int) -> dict:
        """Keyword arguments the interpreter forwards to
        :meth:`_trace_keys` — a backend that knows its slot -> shard
        owner mapping (tpu) returns ``cap_per_shard``/``n_shards`` so
        window records carry the per-destination row split."""
        return {}

    def _prim_window_dedup(self, flat, fgrads, fcounts, capacity: int):
        """Backend dedup primitive: collapse repeated slots of the
        flattened window into their first occurrence, summing grads and
        counts.  Returns ``(ded_slots, ded_grads, ded_counts)`` — same
        leading shape with non-representatives marked ``-1`` (device
        routes) or compacted unique rows (the eager oracle).

        Default: the single-device representative trick (sort-free
        positional scatter-min over a (capacity+1,) plane), which any
        one-program
        device backend can use as-is."""
        B = flat.shape[0]
        valid = flat >= 0
        pos = jnp.arange(B, dtype=jnp.int32)
        safe = jnp.where(valid, flat, capacity)
        rep = jnp.full((capacity + 1,), B, jnp.int32).at[safe].min(
            jnp.where(valid, pos, B), mode="drop")
        owner = jnp.where(valid, jnp.take(rep, safe), B)
        is_owner = valid & (owner == pos)
        ded_grads = {}
        for f, g in fgrads.items():
            g = jnp.asarray(g)
            ded_grads[f] = jnp.zeros_like(g).at[owner].add(
                g * valid[:, None].astype(g.dtype), mode="drop")
        ded_counts = jnp.zeros(fcounts.shape, jnp.float32).at[owner].add(
            fcounts * valid, mode="drop")
        return jnp.where(is_owner, flat, -1), ded_grads, ded_counts

    def _prim_ef_drain(self, state, ded_slots, ded_grads, capacity: int,
                       quant: str):
        """Backend EF primitive: drain residual planes into the deduped
        sums, quantize-dequantize the values, bank the new error.
        Returns ``(state', ded_grads')``.  The numerics/trace taps fire
        inside :func:`ef_quantize_window` (device twin) or the local
        oracle's numpy override — both under the interpreter's plan."""
        return ef_quantize_window(state, ded_slots, ded_grads, capacity,
                                  quant, trace_backend=self.name)

    def _prim_window_exchange(self, state, ded_slots, ded_grads,
                              ded_counts, access, mean: bool,
                              need_counts: bool, wire):
        """Backend exchange primitive for a deduped, encoded window:
        ship the surviving rows, booking the exchange at the plan's
        encoded ``(row_bytes, base_bytes)``.  Default: the span family
        (counts always ride — the oracle routes' legacy contract)."""
        return self.push_span(state, ded_slots, ded_grads, ded_counts,
                              access, mean=mean, _wire=wire)

    def _prim_sparse_allreduce(self, state, flat, fgrads, access,
                               mean: bool, fcounts):
        """Backend sparse-allreduce primitive: reconcile the window's
        touched-row (index, value) set into the full table — the
        ``sparse_allreduce`` collective of the window ``dense`` rung
        (Ok-Topk's split-and-exchange; see transfer/sparse_allreduce).
        Default: the single-program twin — scatter-add merge of
        duplicate indices + full-table apply, exactly what the
        reduce-scatter/allgather degenerates to on a one-program world
        (serves the xla backend and the base class).  Distributed
        backends override with the real exchange (tpu: the tiled
        ``psum_scatter`` already IS the balanced reduce-scatter landing
        each reduced slice on its sharded owner, so no allgather is
        needed — only the ledger booking differs from the dense
        collective there)."""
        from swiftmpi_tpu.transfer.sparse_allreduce import (merge_counts,
                                                            merge_rows)
        capacity = next(iter(state.values())).shape[0]
        dense = {f: merge_rows(flat, jnp.asarray(g), capacity)
                 for f, g in fgrads.items()}
        if mean:
            counts = (fcounts if fcounts is not None
                      else jnp.ones(flat.shape, jnp.float32))
            csum = merge_counts(flat, counts, capacity)
            inv = (1.0 / jnp.maximum(csum, 1.0))[:, None]
            dense = {f: a * inv for f, a in dense.items()}
        new_fields = access.apply_push(state, dense)
        out = dict(state)
        out.update(new_fields)
        ok = (flat >= 0) & (flat < capacity)
        return bump_row_versions(out, state,
                                 jnp.where(ok, flat, capacity))

    def _interpret_window_flat(self, state, flat, fgrads, access,
                               mean: bool, fcounts, pre_deduped=False,
                               passthrough=None):
        """Execute one compiled plan over a flattened W > 1 window.

        ``pre_deduped``: the rows already went through a unified-space
        dedup (the hybrid hot-split stage) — skip the dedup primitive
        and book a traced-zero coalesce so the decision still lands on
        this backend's ledger/trace.  ``passthrough``: the original
        ``(slots, grads, counts)`` triple, supplied by routes whose
        dense/sparse decisions execute as the legacy passthrough."""
        from swiftmpi_tpu.transfer.plan import window_route
        route = window_route(self.name)
        capacity = next(iter(state.values())).shape[0]
        with_counts = ((fcounts is not None) if route.counts_follow_data
                       else True)
        row_bytes = grad_row_bytes(fgrads, with_counts=with_counts)
        quant = self.wire_quant
        qrb = (quant_grad_row_bytes(fgrads, quant,
                                    with_counts=with_counts)
               if quant != "off" else None)
        plan = self._window_plan(flat.shape[0], capacity, row_bytes,
                                 quant_row_bytes=qrb, family="window",
                                 with_counts=with_counts)
        spec = plan.spec
        decision = plan.wire_format
        if decision == "dense" and route.always_decide:
            self._count_collective(plan.collective)
            if plan.collective == "sparse_allreduce":
                if getattr(self, "count_traffic", False):
                    from swiftmpi_tpu.transfer.sparse_allreduce import \
                        ROW_ID_BYTES
                    val_bytes = grad_row_bytes(fgrads, with_index=False,
                                               with_counts=mean)
                    # semantic sparse payload: touched (index, value)
                    # rows by occupancy — duplicate slots merge for
                    # free in the local scatter-add, so only unique
                    # rows pay wire (the booking the budget gate and
                    # price_hot_collectives both model)
                    valid = (flat >= 0) & (flat < capacity)
                    safe = jnp.where(valid, flat, capacity)
                    occ = jnp.zeros((capacity + 1,), jnp.int32).at[
                        safe].add(1, mode="drop")
                    touched = jnp.sum(occ[:capacity] > 0)
                    self._record_exchange(touched,
                                          ROW_ID_BYTES + val_bytes,
                                          decision="dense")
                    self._record_saved(
                        capacity * val_bytes
                        - touched * (ROW_ID_BYTES + val_bytes))
                return self._prim_sparse_allreduce(
                    state, flat, fgrads, access, mean, fcounts)
            if getattr(self, "count_traffic", False):
                # wire volume is the static table size, not the row
                # count — the `flat[0] * 0 + capacity` token keeps the
                # value traced so the callback fires once per compiled
                # execution
                self._record_exchange(
                    flat[0].astype(jnp.int32) * 0 + capacity,
                    grad_row_bytes(fgrads, with_index=False,
                                   with_counts=mean),
                    decision="dense")
            return self._push_window_dense(state, flat, fgrads, access,
                                           mean, fcounts)
        if not spec.dedup and not route.dedups_lossless:
            # oracle routes execute dense/sparse as the legacy
            # passthrough; the decision is still booked (traced zero
            # keeps the callback firing once per compiled execution)
            if route.eager:
                self._record_coalesce(0, 0, decision=decision)
            elif getattr(self, "count_traffic", False):
                zero = jnp.sum(flat >= 0) * 0
                self._record_coalesce(zero, zero, decision=decision)
            slots0, grads0, counts0 = passthrough
            return self._push_window_passthrough(
                state, slots0, grads0, access, mean=mean, counts=counts0)
        # dedup stage (plan taps: keys reservoir BEFORE the coalesce
        # callback opens the window record, obs/trace.py)
        if pre_deduped:
            ded_slots, ded_grads, ded_counts = flat, fgrads, fcounts
            self._trace_keys(ded_slots, **self._trace_shard_args(capacity))
            if getattr(self, "count_traffic", False):
                # the hot-split stage already logged the dedup row
                # deltas on its own ledger, but the wire decision is
                # made HERE — log it with zero row deltas
                zero = jnp.sum(flat >= 0) * 0
                self._record_coalesce(zero, zero, decision=decision)
        else:
            ded_slots, ded_grads, ded_counts = self._prim_window_dedup(
                flat, fgrads, fcounts, capacity)
            self._trace_keys(ded_slots, **self._trace_shard_args(capacity))
            if route.eager:
                self._record_coalesce(int((flat >= 0).sum()),
                                      int((ded_slots >= 0).sum()),
                                      decision=decision)
            elif getattr(self, "count_traffic", False):
                self._record_coalesce(jnp.sum(flat >= 0),
                                      jnp.sum(ded_slots >= 0),
                                      decision=decision)
        if spec.ef:
            state, ded_grads = self._prim_ef_drain(
                state, ded_slots, ded_grads, capacity, quant)
        # mean needs the original contribution multiplicities (dedup
        # collapsed them into ded_counts); plain sums need no counts at
        # all — pre-summing commutes with the owner-side segment sum
        need_counts = ((mean or with_counts) if route.counts_follow_data
                       else True)
        wire = (plan.spec.wire(ded_grads, quant, capacity, need_counts)
                if spec.encoded else None)
        return self._prim_window_exchange(state, ded_slots, ded_grads,
                                          ded_counts, access, mean,
                                          need_counts, wire)

    def _interpret_window_hot_split(self, state, flat, fgrads, fcounts,
                                    access, mean: bool,
                                    counts_present: bool):
        """Execute the ``hot_split`` placement (hybrid): pad, dedup ONCE
        in the unified slot space, reconcile the hot slice with the
        dense psum primitive, re-interpret the tail slice on the tail
        backend (``pre_deduped`` — the dedup pass is not paid twice).
        Uses the hybrid backend's structural primitives (``_pad_batch``,
        ``_split_state``, ``_hot_push``) — only reachable on routes
        declaring ``placement="hot_split"``."""
        from swiftmpi_tpu.parameter.sparse_table import hot_name
        flat, fgrads, fcounts, _ = self._pad_batch(flat, fgrads, fcounts)
        tail_state, hot_state = self._split_state(state)
        n_hot = self._n_hot(state)
        if n_hot == 0:
            return self.tail._interpret_window_flat(
                tail_state, flat, fgrads, access, mean, fcounts)
        cap_tail = next(iter(tail_state.values())).shape[0]
        ded_slots, ded_grads, ded_counts = self.tail._prim_window_dedup(
            flat, fgrads, fcounts, n_hot + cap_tail)
        if self.count_traffic:
            self._record_coalesce(jnp.sum(flat >= 0),
                                  jnp.sum(ded_slots >= 0))
        is_hot = (ded_slots >= 0) & (ded_slots < n_hot)
        tail_slots = jnp.where(ded_slots >= n_hot, ded_slots - n_hot, -1)
        # hot-plane TrafficPlan: the collective decision (psum vs
        # sparse_allreduce, transfer/plan.py compile_hot_plan) is made
        # HERE — backends only execute the primitive the plan names.
        # width_bytes includes the f32 counts column (+4), which is
        # also the sparse wire's per-row index cost, so the same number
        # prices both collectives
        width_bytes = sum(
            np.dtype(jnp.asarray(g).dtype).itemsize * g.shape[1]
            for g in ded_grads.values()) + 4
        hot_plan = self._hot_plan(n_hot, width_bytes)
        sparse_ar = hot_plan.collective == "sparse_allreduce"
        self._count_collective(hot_plan.collective)
        # stage the hot/tail split for the wire tracer under the TAIL's
        # name: the tail backend owns the decision-carrying window
        # record this callback's extras attach to (obs/trace.py)
        tr = obs.get_tracer()
        if tr is not None:
            hot_rows = jnp.sum(is_hot)
            cb = (lambda v, _tr=tr, _n=self.tail.name,
                  _c=hot_plan.collective:
                  _tr.stage(_n, hot_rows=int(v), hot_collective=_c))
            if isinstance(hot_rows, jax.core.Tracer):
                jax.debug.callback(cb, hot_rows)
            else:
                cb(hot_rows)
        # mean normalization now depends on the collapsed
        # multiplicities, so both slices take the counts wire format
        need_counts = mean or counts_present
        new_tail = self.tail._interpret_window_flat(
            tail_state, tail_slots, ded_grads, access, mean,
            ded_counts if need_counts else None, pre_deduped=True)
        if sparse_ar:
            if self.count_traffic:
                # semantic sparse payload: ded_slots hold one
                # representative per slot PER SHARD (the tpu dedup is
                # device-local), so the hot mask sum is exactly the sum
                # of per-shard contributed (index, value) sets — the
                # volume each shard feeds the reduce-scatter — with the
                # delta vs the dense model landing on
                # hot_psum_bytes_saved
                touched = jnp.sum(is_hot)
                self._record_hot_sparse(touched, width_bytes)
                self._record_exchange(touched, width_bytes)
                self._record_saved((n_hot - touched) * width_bytes)
            new_hot = self._hot_push_sparse(
                hot_state, ded_slots, ded_grads, access, mean,
                ded_counts if need_counts else None)
        else:
            if self.count_traffic:
                self._record_hot(jnp.sum(is_hot), n_hot * width_bytes)
                self._record_exchange(jnp.sum(is_hot) * 0 + n_hot,
                                      width_bytes)
            new_hot = self._hot_push(hot_state, ded_slots, ded_grads,
                                     access, mean,
                                     ded_counts if need_counts else None)
        out = dict(new_tail)
        out.update({hot_name(f): v for f, v in new_hot.items()})
        return out


def get_transfer(name: Optional[str] = None,
                 config: Optional[ConfigParser] = None,
                 **kwargs) -> Transfer:
    """Resolve a backend by name or by the ``[cluster] transfer`` config key
    (the BASELINE.json ``transfer=tpu`` flag)."""
    if name is None:
        if config is not None and config.has("cluster", "transfer"):
            name = config.get("cluster", "transfer").to_string()
        else:
            name = "xla"
    if name == "xla":
        from swiftmpi_tpu.transfer.xla import XlaTransfer
        return XlaTransfer(**kwargs)
    if name == "tpu":
        from swiftmpi_tpu.transfer.tpu import TpuTransfer
        return TpuTransfer(**kwargs)
    if name == "hybrid":
        from swiftmpi_tpu.transfer.hybrid import HybridTransfer
        return HybridTransfer(**kwargs)
    if name == "local":
        from swiftmpi_tpu.transfer.local import LocalTransfer
        return LocalTransfer(**kwargs)
    raise ValueError(f"unknown transfer backend {name!r} "
                     "(expected xla|tpu|hybrid|local)")
