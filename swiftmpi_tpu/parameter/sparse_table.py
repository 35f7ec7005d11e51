"""Row-sharded dense parameter table in HBM.

TPU-native equivalent of the reference server store
(`/root/reference/src/parameter/sparsetable.h:17-149`): instead of
``shard_num`` dense_hash_maps behind RWLocks in a server process, the table
is a pytree of dense ``(capacity, dim)`` arrays living sharded across device
HBM, indexed by the dense slots a host-side KeyIndex assigns.  The
reference's two-level routing (key → server via hashfrag, key → shard via
murmur % shard_num) collapses into the KeyIndex slot layout: shard *i* owns
slot range ``[i*cap, (i+1)*cap)``, which is exactly device *i*'s row slice
under a ``PartitionSpec(axis)`` sharding.

Lazy row init (accessmethod.h:63-70: create + ``init_param`` on first pull)
becomes eager whole-capacity initialization with the same per-row
distribution: untouched rows are never observed, so eager-random ≡
lazy-random in all observable behavior, and the device never round-trips to
the host to materialize a row.

The table *state* is a plain ``{field: jax.Array}`` dict — a pytree that
training steps close over, donate, and return updated; the ``SparseTable``
object is the host-side handle (spec, mesh placement, key index).  Each
array is ``(capacity, FieldSpec.dim)``: the STORED row, which may be wider
than the field's logical row (``access.stored_width``: a 300-wide row is
kept on 384 lanes, zeros beyond, so that the compiler's default layout is
the row-major one and no step copies a whole field).  Rows are pulled,
multiplied and pushed at their stored width; ``unified_rows_host``, text
dumps and embedding exports cut them to the logical one.

Window-coalesced updates and the AdaGrad accumulator: with ``[cluster]
push_window: W`` the transfer layer sums a window's W per-step gradient
batches into ONE push, so the access rule — including the ``*2sum``
AdaGrad accumulator rows this table stores — runs once per unique row
per window instead of once per step.  At ``W == 1`` the coalesced push
is the flatten of a unit axis and the update is bit-identical to the
per-step path.  At ``W > 1`` two bounded deviations apply: (a) steps
inside a window read the window-start snapshot, so a row's gradient can
be up to W-1 steps stale, and (b) the accumulator advances once with
``(Σg)²`` instead of W times with ``Σ(g²)`` — by Cauchy-Schwarz
``(Σg)² ≤ W·Σg²``, so one window adds at most W× a step's mass when the
window's gradients align, and as little as 0 when they cancel: the
effective AdaGrad step size drifts within a factor-of-√W band of the
per-step trajectory.  Both effects vanish as W→1 and are characterized in
docs/ARCHITECTURE.md "Window-coalesced push"; parity tests pin the
envelope in tests/test_window_push.py.

Hybrid hot/cold placement: when the KeyIndex carries a
``HotColdPartition``, each field ``f`` splits into a row-sharded tail array
under its plain name (indexed by ``slot - n_hot``) and a REPLICATED hot
array under ``f + "@hot"`` of shape ``(n_hot, dim)`` (indexed by the hot
slot directly).  The unified slot space ``concat(hot, tail)`` is what
callers see through :meth:`gather` / :meth:`unified_rows_host`.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from swiftmpi_tpu.cluster.mesh import MODEL_AXIS
from swiftmpi_tpu.parameter.access import AccessMethod
from swiftmpi_tpu.parameter.key_index import KeyIndex

TableState = Dict[str, jax.Array]

#: suffix marking a replicated hot-head array in a table state dict
HOT_SUFFIX = "@hot"


def hot_name(field: str) -> str:
    return field + HOT_SUFFIX


def is_hot_field(name: str) -> bool:
    return name.endswith(HOT_SUFFIX)


def base_field(name: str) -> str:
    """Strip the hot suffix: ``"v@hot" -> "v"``, plain names unchanged."""
    return name[:-len(HOT_SUFFIX)] if is_hot_field(name) else name


#: suffix marking an error-feedback residual plane in a table state
#: dict: ``"v@ef"`` holds, per TAIL row, the quantization error of v's
#: gradients not yet applied (drained into the row's next quantized
#: window push).  Tail-shaped, f32, row-sharded; NOT an access field —
#: pushes route around it and pulls never see it, it simply rides the
#: state pytree like the ``@hot`` overlays do.
EF_SUFFIX = "@ef"


def ef_name(field: str) -> str:
    return field + EF_SUFFIX


def is_ef_field(name: str) -> bool:
    return name.endswith(EF_SUFFIX)


#: name of the per-row version plane in a table state dict: one
#: ``(capacity, 1)`` int32 array stamping every TAIL row with the
#: per-shard-monotonic version of its last apply.  The delta-pull plane
#: (transfer/pull_cache.py) compares these stamps against the worker's
#: watermark to decide which pulled rows actually need bytes on the
#: wire.  Tail-shaped, row-sharded; NOT an access field — pushes bump
#: it as part of their apply, pulls gather it alongside the value rows
#: when the cache is armed, and it otherwise rides the state pytree
#: like the ``@ef`` planes do.  Hot rows carry no versions: the hybrid
#: replica is reconciled by a dense psum every window and pull hits on
#: it are already booked at 0 bytes.
ROWVER_KEY = "@rowver"


def has_row_versions(state) -> bool:
    return ROWVER_KEY in state


class SparseTable:
    def __init__(self, access: AccessMethod, key_index: KeyIndex,
                 mesh: Optional[Mesh] = None, axis: str = MODEL_AXIS,
                 seed: int = 0):
        self.access = access
        self.key_index = key_index
        self.mesh = mesh
        self.axis = axis
        self.seed = int(seed)
        if mesh is not None:
            axis_size = mesh.shape[axis]
            if key_index.num_shards % axis_size:
                raise ValueError(
                    f"num_shards={key_index.num_shards} must be a multiple "
                    f"of mesh axis {axis!r} size {axis_size}")
        self.state: TableState = self._init_state()

    # -- construction -----------------------------------------------------
    def row_sharding(self) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, PartitionSpec(self.axis))

    def replicated_sharding(self) -> Optional[NamedSharding]:
        """Placement of hot-head arrays: one full copy per device."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, PartitionSpec())

    @property
    def n_hot(self) -> int:
        return self.key_index.n_hot

    def field_sharding(self, name: str) -> Optional[NamedSharding]:
        """Sharding for a state-dict entry by name (hot → replicated)."""
        return (self.replicated_sharding() if is_hot_field(name)
                else self.row_sharding())

    def _init_state(self) -> TableState:
        return self._init_program()(jax.random.key(self.seed))

    def _init_program(self):
        """The jitted ``key -> state`` that draws every row."""
        cap = self.key_index.capacity
        n_hot = self.n_hot
        fields = self.access.fields

        def init_all(key):
            out = {}
            for name, fs in sorted(fields.items()):
                key, sub = jax.random.split(key)
                out[name] = fs.draw(sub, cap)
            # hot arrays draw from the same stream AFTER the tail fields,
            # so a table with n_hot=0 is bit-identical to the pre-hybrid
            # layout
            for name, fs in sorted(fields.items()):
                if n_hot:
                    key, sub = jax.random.split(key)
                    out[hot_name(name)] = fs.draw(sub, n_hot)
            return out

        sharding = self.row_sharding()
        if sharding is None:
            return jax.jit(init_all)
        shardings = {name: sharding for name in fields}
        if n_hot:
            rep = self.replicated_sharding()
            shardings.update({hot_name(name): rep for name in fields})
        return jax.jit(init_all, out_shardings=shardings)

    def ensure_ef(self, grad_fields) -> None:
        """Arm error-feedback residual planes for ``grad_fields``: one
        zero-initialized tail-shaped ``<f>@ef`` f32 array per field,
        row-sharded like the field's tail.  Idempotent — existing
        planes (e.g. restored from a checkpoint) are left alone.  Hot
        rows need no residuals: the hybrid backend reconciles them with
        a dense psum that never quantizes."""
        sharding = self.row_sharding()
        cap = self.key_index.capacity
        for f in grad_fields:
            name = ef_name(f)
            if name in self.state:
                continue
            fs = self.access.fields[f]
            z = jnp.zeros((cap, fs.dim), jnp.float32)
            if sharding is not None:
                z = jax.device_put(z, sharding)
            self.state[name] = z

    @property
    def ef_fields(self):
        """Names of the armed residual planes (``[] when EF is off``)."""
        return [f for f in self.state if is_ef_field(f)]

    def ensure_row_versions(self) -> None:
        """Arm the per-row version plane: one zero-initialized
        ``(capacity, 1)`` int32 tail-shaped array under
        :data:`ROWVER_KEY`, row-sharded like the fields it stamps.
        Idempotent — an existing plane (e.g. restored from a
        checkpoint) is left alone, so versions keep counting up across
        restarts and a resumed worker's cold cache can never collide
        with a stale stamp.  Version 0 means "never applied"; every
        push path bumps touched rows to ``max(local shard) + 1``, which
        is monotonic per shard with no host-side counter."""
        if ROWVER_KEY in self.state:
            return
        z = jnp.zeros((self.key_index.capacity, 1), jnp.int32)
        sharding = self.row_sharding()
        if sharding is not None:
            z = jax.device_put(z, sharding)
        self.state[ROWVER_KEY] = z

    # -- growth ------------------------------------------------------------
    def grow(self, new_capacity_per_shard: Optional[int] = None) -> None:
        """Re-lay-out the table at a larger per-shard capacity (default
        2x), preserving every occupied row (params AND optimizer state)
        and freshly initializing the new slots.

        The reference never needs this — ``dense_hash_map`` grows by
        itself (sparsetable.h) — but dense static-shape HBM arrays don't,
        so growth is an explicit re-shard: old rows scatter into their new
        ``shard * new_cap + local`` positions in one jitted remap (no
        donation — both layouts coexist during the scatter, so budget one
        extra copy of the table).  Mesh sharding is preserved (num_shards
        is unchanged, so per-device shard ranges still line up)."""
        ki = self.key_index
        old_per = ki.capacity_per_shard
        new_per = int(new_capacity_per_shard or 2 * old_per)
        n_hot = self.n_hot
        # hot rows are untouched by growth (their slots sit below n_hot
        # and never move); only tail rows re-stride
        items = [(k, s) for k, s in ki.items() if s >= n_hot]
        old_rows = np.asarray([s - n_hot for _, s in items], np.int64)
        ki.grow(new_per)                      # remaps key -> new slot
        # same remap the index applied, vectorized: shard and local parts
        # are preserved, only the stride changes
        new_rows = (old_rows // old_per) * new_per + old_rows % old_per

        fields = self.access.fields
        sharding = self.row_sharding()
        new_cap = ki.capacity
        # fresh init stream for the enlarged arrays: a different fold per
        # growth so re-grown slots never repeat earlier row inits
        self.seed += 1

        def remap(old_state, old_rows, new_rows, key):
            out = {}
            for name, fs in sorted(fields.items()):
                key, sub = jax.random.split(key)
                arr = fs.draw(sub, new_cap)
                if len(items):
                    arr = arr.at[new_rows].set(
                        old_state[name][old_rows])
                out[name] = arr
            return out

        tail_state = {f: v for f, v in self.state.items()
                      if not is_hot_field(f)}
        # no donation: the enlarged outputs can't reuse the smaller input
        # buffers anyway, and both copies must coexist during the scatter
        jitted = jax.jit(
            remap,
            out_shardings=None if sharding is None
            else {name: sharding for name in fields})
        new_state = jitted(tail_state, jnp.asarray(old_rows),
                           jnp.asarray(new_rows),
                           jax.random.key(self.seed))
        # replicated hot arrays ride through unchanged
        for f, v in self.state.items():
            if is_hot_field(f):
                new_state[f] = v
        # EF residual planes re-stride with the tail rows they describe;
        # new slots start with zero residual (nothing pending by
        # construction)
        for f, v in self.state.items():
            if not is_ef_field(f):
                continue
            arr = jnp.zeros((new_cap, v.shape[1]), v.dtype)
            if len(items):
                arr = arr.at[jnp.asarray(new_rows)].set(
                    v[jnp.asarray(old_rows)])
            if sharding is not None:
                arr = jax.device_put(arr, sharding)
            new_state[f] = arr
        # the row-version plane re-strides with its rows exactly like
        # the EF planes; fresh slots start at version 0 ("never
        # applied").  Workers flush their pull caches on any capacity
        # change (the shadow keys on capacity), so carried stamps can
        # never false-hit against pre-growth cache entries even though
        # the row ids they stamp just moved.
        if ROWVER_KEY in self.state:
            v = self.state[ROWVER_KEY]
            arr = jnp.zeros((new_cap, v.shape[1]), v.dtype)
            if len(items):
                arr = arr.at[jnp.asarray(new_rows)].set(
                    v[jnp.asarray(old_rows)])
            if sharding is not None:
                arr = jax.device_put(arr, sharding)
            new_state[ROWVER_KEY] = arr
        self.state = new_state

    # -- online re-partition ----------------------------------------------
    def repartition(self, new_partition) -> "object":
        """Swap the hot/cold split to ``new_partition`` (a
        ``HotColdPartition`` or None), replaying the KeyIndex's
        :class:`~swiftmpi_tpu.parameter.key_index.RepartitionPlan` on
        the device arrays: demoted hot rows are written back into their
        tail slots, staying keys' hot rows move to their new frequency
        rank, and promoted keys seed their hot row from their
        materialized tail row (or fresh init if never touched).  Tail
        rows never re-stride — a promoted key's tail slot stays
        allocated and merely goes dormant under the hot overlay, so a
        later demotion writes the live hot row back over it.

        Like :meth:`grow`, the remap is one jitted scatter with no
        donation (both layouts coexist during the copy) and anything
        jitted over the OLD state dict must be rebuilt by the caller
        (the safe-point contract in models/word2vec.py).  Raises
        ``CapacityError`` before touching anything when demoted keys
        cannot get tail slots."""
        plan = self.key_index.repartition(new_partition)
        old_n_hot, new_n_hot = plan.old_n_hot, plan.new_n_hot

        fields = self.access.fields
        sharding = self.row_sharding()
        self.seed += 1        # fresh init stream for the new hot head

        def remap(state, p, key):
            out = {}
            for name, fs in sorted(fields.items()):
                tail = state[name]
                if p["demote_src"].shape[0]:
                    tail = tail.at[p["demote_dst"]].set(
                        jnp.take(state[hot_name(name)], p["demote_src"],
                                 axis=0))
                out[name] = tail
            for name, fs in sorted(fields.items()):
                if not new_n_hot:
                    continue
                key, sub = jax.random.split(key)
                hot = fs.draw(sub, new_n_hot)
                if p["hot_from_hot_src"].shape[0]:
                    hot = hot.at[p["hot_from_hot_dst"]].set(
                        jnp.take(state[hot_name(name)],
                                 p["hot_from_hot_src"], axis=0))
                if p["hot_from_tail_src"].shape[0]:
                    # reads the OLD tail (state[name]), not the demoted-
                    # updated copy: a promoted key's seed row predates
                    # this repartition by construction
                    hot = hot.at[p["hot_from_tail_dst"]].set(
                        jnp.take(state[name], p["hot_from_tail_src"],
                                 axis=0))
                out[hot_name(name)] = hot
            return out

        state_in = dict(self.state)
        if old_n_hot == 0:
            # no hot arrays exist yet; remap indexes them only under
            # zero-length plan arrays, but the dict entries must exist
            for name, fs in sorted(fields.items()):
                state_in[hot_name(name)] = jnp.zeros(
                    (0, fs.dim), fs.dtype)
        p = {k: jnp.asarray(getattr(plan, k)) for k in
             ("demote_src", "demote_dst", "hot_from_hot_src",
              "hot_from_hot_dst", "hot_from_tail_src",
              "hot_from_tail_dst")}
        out_shardings = None
        if sharding is not None:
            out_shardings = {name: sharding for name in fields}
            if new_n_hot:
                rep = self.replicated_sharding()
                out_shardings.update(
                    {hot_name(name): rep for name in fields})
        jitted = jax.jit(remap, out_shardings=out_shardings)
        new_state = jitted(state_in, p, jax.random.key(self.seed))
        # EF residual planes are tail-indexed and tail rows never
        # re-stride under repartition, so they carry through unchanged.
        # A promoted key's residual freezes with its dormant tail slot
        # (the hot psum path never quantizes) and drains on a later
        # demotion — one stale bounded-by-a-window quantization error,
        # within the documented EF envelope.
        for f, v in self.state.items():
            if is_ef_field(f):
                new_state[f] = v
        # row-version plane: tail rows keep their stamps (their ids are
        # stable under repartition), but a demoted key's tail slot just
        # had the live hot row written over it — bump those rows past
        # the global max so any cached copy of the dormant pre-promotion
        # value is invalidated.
        if ROWVER_KEY in self.state:
            ver = self.state[ROWVER_KEY]
            if plan.demote_dst.shape[0]:
                newv = jnp.max(ver) + jnp.int32(1)
                ver = ver.at[jnp.asarray(plan.demote_dst)].set(newv)
                if sharding is not None:
                    ver = jax.device_put(ver, sharding)
            new_state[ROWVER_KEY] = ver
        self.state = new_state
        return plan

    # -- device-level row access ------------------------------------------
    def _take_unified(self, field: str, slots) -> jax.Array:
        """Row gather over the unified hot+tail slot space."""
        tail = self.state[field]
        n_hot = self.n_hot
        if not n_hot:
            return jnp.take(tail, slots, axis=0)
        hot = self.state[hot_name(field)]
        hot_rows = jnp.take(hot, jnp.clip(slots, 0, n_hot - 1), axis=0)
        tail_rows = jnp.take(
            tail, jnp.clip(slots - n_hot, 0, tail.shape[0] - 1), axis=0)
        return jnp.where((slots < n_hot)[..., None], hot_rows, tail_rows)

    def gather(self, slots) -> TableState:
        """Rows for ``slots`` across pull-visible fields (device op)."""
        slots = jnp.asarray(slots)
        return {f: self._take_unified(f, slots)
                for f in self.access.pull_fields}

    def gather_all_fields(self, slots) -> TableState:
        slots = jnp.asarray(slots)
        return {f: self._take_unified(f, slots)
                for f in self.access.fields}

    # -- host-level introspection -----------------------------------------
    @property
    def capacity(self) -> int:
        return self.key_index.capacity

    @property
    def num_rows(self) -> int:
        """Occupied rows (reference SparseTable::size, sparsetable.h:135)."""
        return len(self.key_index)

    def rows_as_numpy(self) -> Dict[str, np.ndarray]:
        from swiftmpi_tpu.cluster.bootstrap import host_array

        return {f: host_array(v) for f, v in self.state.items()}

    def unified_rows_host(self, field: str) -> np.ndarray:
        """Host copy of ``field`` indexed by UNIFIED slot: rows
        ``[0, n_hot)`` are the replicated hot head, rows ``[n_hot, ...)``
        the sharded tail, cut to the field's logical width (the stored
        row may be wider: `access.stored_width`).  This is the view
        checkpoint text dumps and embedding exports index with KeyIndex
        slots."""
        from swiftmpi_tpu.cluster.bootstrap import host_array

        width = self.access.fields[field].logical
        tail = host_array(self.state[field])[:, :width]
        if not self.n_hot:
            return tail
        return np.concatenate(
            [host_array(self.state[hot_name(field)])[:, :width], tail],
            axis=0)

    def __repr__(self) -> str:  # pragma: no cover
        return (f"SparseTable(fields={list(self.access.fields)}, "
                f"capacity={self.capacity}, rows={self.num_rows}, "
                f"sharded={self.mesh is not None})")
