"""Checkpoint out / load for sparse tables.

Two formats:

* **Text** — line-per-key ``key\\t<value>`` dumps, the reference's only
  checkpoint format (`/root/reference/src/parameter/sparsetable.h:119-132`,
  written at ``finalize``; value layout is app-defined via ``operator<<``,
  e.g. word2vec writes ``v... \\t h...`` — word2vec.h:100-110).  ``load``
  supports the reference's ownership filter (``ClusterServer::load`` keeps
  only rows the local server owns, server.h:49-62) via ``shard_filter``.
* **Binary (npz)** — full-fidelity mid-training checkpoints including
  optimizer state and the key index, which the reference cannot do (its
  dump drops h2sum/v2sum — SURVEY.md §5 "Checkpoint/resume: partial").

Formatters/parsers turn a ``{field: row}`` dict into the app's text value
and back; models provide reference-compatible ones.
"""

from __future__ import annotations

import glob
import os
import time
import zlib
from typing import Callable, Dict, List, Optional

import numpy as np

from swiftmpi_tpu import obs
from swiftmpi_tpu.cluster.bootstrap import host_array, is_writer
from swiftmpi_tpu.parameter.sparse_table import (ROWVER_KEY, SparseTable,
                                                 base_field, hot_name,
                                                 is_ef_field)

Formatter = Callable[[Dict[str, np.ndarray]], str]
Parser = Callable[[str], Dict[str, np.ndarray]]


def default_formatter(fields) -> Formatter:
    """Space-joined values per field, tab between fields, in given order."""
    def fmt(row: Dict[str, np.ndarray]) -> str:
        return "\t".join(
            " ".join(repr(float(x)) for x in np.ravel(row[f]))
            for f in fields)
    return fmt


def default_parser(fields) -> Parser:
    def parse(text: str) -> Dict[str, np.ndarray]:
        parts = text.split("\t")
        return {f: np.array([float(x) for x in p.split()], np.float32)
                for f, p in zip(fields, parts)}
    return parse


# -- text (reference-compatible) ------------------------------------------

def _lookup_growing(table: SparseTable, keys) -> np.ndarray:
    """key_index.lookup that grows the table on capacity exhaustion — a
    checkpoint written after auto-growth must load back into a model built
    with the original (smaller) capacity."""
    from swiftmpi_tpu.parameter.key_index import CapacityError

    while True:
        try:
            return table.key_index.lookup(keys)
        except CapacityError:
            table.grow()


def _index_arrays(key_index):
    n = len(key_index)
    keys = np.empty(n, np.uint64)
    slots = np.empty(n, np.int64)
    for i, (k, s) in enumerate(key_index.items()):
        keys[i] = k
        slots[i] = s
    return keys, slots


def dump_table_text(table: SparseTable, path: str,
                    formatter: Optional[Formatter] = None,
                    fields: Optional[tuple] = None) -> int:
    """Write ``key\\tvalue`` lines for every occupied row; returns count.

    With no custom ``formatter`` the value layout is the ``fields`` order
    (default: the access method's pull fields), each a space-joined float
    vector, tab-separated — and the write runs through the native C++
    writer (io.cpp smtpu_dump_rows) when available."""
    fields = tuple(fields or table.access.pull_fields)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    if formatter is None:
        from swiftmpi_tpu.data import native
        if native.available():
            keys, slots = _index_arrays(table.key_index)
            # unified_rows_host is a collective in multi-process runs:
            # gather on every process, write once.  Unified view: hot
            # rows first, tail rows offset — slots index it directly.
            arrs = [table.unified_rows_host(f)[slots] for f in fields]
            if not is_writer():
                return len(keys)
            return native.dump_rows_native(path, keys, arrs)
        formatter = default_formatter(fields)
    rows = {f: table.unified_rows_host(f) for f in table.access.fields}
    if not is_writer():
        return len(table.key_index)
    n = 0
    with open(path, "w") as f:
        for key, slot in table.key_index.items():
            row = {name: arr[slot] for name, arr in rows.items()}
            f.write(f"{key}\t{formatter(row)}\n")
            n += 1
    return n


def load_table_text(table: SparseTable, path: str,
                    parser: Optional[Parser] = None,
                    shard_filter: Optional[int] = None,
                    fields: Optional[tuple] = None) -> int:
    """Stream ``key\\tvalue`` lines into the table, creating slots lazily;
    with ``shard_filter`` keep only keys owned by that shard (the reference
    per-server load filter, server.h:49-62).  Returns rows loaded.

    With no custom ``parser``, rows are fixed-layout ``fields`` float
    vectors and parsing runs through the native C++ reader when
    available."""
    fields = tuple(fields or table.access.pull_fields)
    if parser is None:
        from swiftmpi_tpu.data import native
        if native.available():
            # the text rows are the logical width, not the stored one
            dims = [table.access.fields[f].logical for f in fields]
            key_arr, arrs = native.load_rows_native(path, dims)
            if not len(key_arr):
                return 0
            if shard_filter is not None:
                keep = table.key_index.shard_of(key_arr) == shard_filter
                key_arr = key_arr[keep]
                arrs = [a[keep] for a in arrs]
                if not len(key_arr):
                    return 0
            idx = np.asarray(_lookup_growing(table, key_arr), np.int32)
            state = dict(table.state)
            for fname, block in zip(fields, arrs):
                _scatter_unified(table, state, fname, idx,
                                 block.reshape(len(idx), -1))
            table.state = state
            return len(key_arr)
        parser = default_parser(fields)
    keys: list = []
    rests: list = []
    with open(path) as f:
        for line in f:
            line = line.rstrip("\n")
            if not line:
                continue
            key_s, _, rest = line.partition("\t")
            keys.append(int(key_s))
            rests.append(rest)
    if not keys:
        return 0
    key_arr = np.asarray(keys, np.uint64)
    if shard_filter is not None:
        keep = table.key_index.shard_of(key_arr) == shard_filter
        key_arr = key_arr[keep]
        rests = [r for r, k in zip(rests, keep) if k]
        if not len(key_arr):
            return 0
    all_slots = _lookup_growing(table, key_arr)
    updates: Dict[str, list] = {f: [] for f in table.access.fields}
    for rest in rests:
        for fname, value in parser(rest).items():
            updates[fname].append(np.asarray(value, np.float32))
    n = len(key_arr)
    slots = all_slots.tolist()
    idx = np.asarray(slots, np.int32)
    state = dict(table.state)
    for fname, vals in updates.items():
        if not vals:
            continue
        block = np.stack(vals).reshape(len(slots), -1)
        _scatter_unified(table, state, fname, idx, block)
    table.state = state
    return n


def _scatter_unified(table: SparseTable, state: dict, fname: str,
                     idx: np.ndarray, block: np.ndarray) -> None:
    """Scatter ``block`` rows at UNIFIED slots ``idx`` into ``state``,
    splitting between the replicated hot array (``slot < n_hot``) and the
    sharded tail (rebased by ``-n_hot``).  Mutates ``state`` in place.
    host_array, not np.asarray, on the read side: state may be a
    non-fully-addressable global array in multi-process runs (the gather
    is collective — every process reaches this line)."""
    n_hot = table.n_hot
    tail_sel = idx >= n_hot
    # text rows carry the logical width; the stored row may be wider
    # (access.stored_width) and its remaining lanes stay zero
    width = block.shape[1]
    arr = host_array(state[fname]).copy()
    arr[idx[tail_sel] - n_hot, :width] = block[tail_sel]
    state[fname] = _replace(table, fname, arr)
    if n_hot and not tail_sel.all():
        hn = hot_name(fname)
        harr = host_array(state[hn]).copy()
        harr[idx[~tail_sel], :width] = block[~tail_sel]
        state[hn] = _replace(table, hn, harr)


def _replace(table: SparseTable, fname: str, arr: np.ndarray):
    import jax
    sharding = table.field_sharding(fname)
    if sharding is None:
        return jax.numpy.asarray(arr)
    return jax.device_put(arr, sharding)


# -- binary (full fidelity, mid-training) ----------------------------------

# orphaned tmp files older than this are swept on the next save; younger
# ones may belong to a concurrent writer mid-savez and must be left alone
_TMP_SWEEP_AGE_S = 300.0
# beyond this age a tmp is swept even if its embedded pid is alive: the
# pid has almost certainly been recycled by an unrelated long-lived
# process (no real savez runs for days), and without a cap such orphans
# would accumulate forever
_TMP_SWEEP_FORCE_AGE_S = 7 * 86400.0


def npz_path(path: str) -> str:
    """Canonical on-disk name for a binary checkpoint (np.savez appends
    .npz itself; every reader/writer must agree on the same name)."""
    return path if path.endswith(".npz") else path + ".npz"


def _writer_alive(tmp_name: str) -> bool:
    """True if the pid embedded in ``<dst>.<pid>.tmp.npz`` is a live
    process — its in-progress write must not be swept (a large-table
    savez can legitimately outlast the normal age threshold; only the
    multi-day force cap overrides this, guarding against pid reuse)."""
    try:
        pid = int(tmp_name.rsplit(".tmp.npz", 1)[0].rsplit(".", 1)[1])
        os.kill(pid, 0)
        return True
    except (ValueError, IndexError, ProcessLookupError):
        return False
    except PermissionError:     # exists, owned by someone else
        return True


# every payload array gets a sibling ``__crc__<name>`` uint32 so loaders
# can detect torn/bit-rotted writes (zip CRCs exist but np.load never
# checks them on the read path we use)
_CRC_PREFIX = "__crc__"


class CheckpointCorruptError(RuntimeError):
    """A checkpoint failed CRC validation or is structurally unreadable.
    Recovery: fall back to an older generation
    (:func:`find_latest_valid_checkpoint`) or restart from scratch."""


def _crc32(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


def atomic_savez(dst: str, payload: Dict[str, np.ndarray]) -> None:
    """Crash-safe npz write: savez to a pid-unique tmp, fsync, then
    rename (+ directory fsync), so a crash mid-write never clobbers the
    last good checkpoint and a rename survives power loss.  Every array
    gains a ``__crc__<name>`` checksum entry for load-time validation.
    Sweeps orphan tmps from killed writers — only when the writing pid
    is dead AND the file has aged (pid check guards long-running
    concurrent writers; the age threshold guards pid reuse)."""
    os.makedirs(os.path.dirname(os.path.abspath(dst)), exist_ok=True)
    tmp = f"{dst}.{os.getpid()}.tmp.npz"   # unique per writer
    now = time.time()
    for stale in glob.glob(glob.escape(dst) + ".*.tmp.npz"):
        if stale == tmp:
            continue
        try:
            age = now - os.path.getmtime(stale)
            if age > _TMP_SWEEP_FORCE_AGE_S or (
                    age > _TMP_SWEEP_AGE_S and not _writer_alive(stale)):
                os.unlink(stale)
        except OSError:
            pass
    full = dict(payload)
    for k in list(payload):
        full[_CRC_PREFIX + k] = np.uint32(_crc32(np.asarray(payload[k])))
    try:
        np.savez(tmp, **full)
        fd = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
        os.replace(tmp, dst)
        try:
            dfd = os.open(os.path.dirname(os.path.abspath(dst)),
                          os.O_RDONLY)
            try:
                os.fsync(dfd)      # make the rename itself durable
            finally:
                os.close(dfd)
        except OSError:
            pass                   # some filesystems refuse dir fsync
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def verify_checkpoint(path: str) -> None:
    """Validate every checksummed array in an npz checkpoint; raises
    :class:`CheckpointCorruptError` on any mismatch or on a structurally
    unreadable file.  Pre-CRC checkpoints (no ``__crc__*`` entries) pass
    — there is nothing to check them against.  A missing file raises
    ``FileNotFoundError`` (absence is not corruption)."""
    p = npz_path(path)
    if not os.path.exists(p):
        raise FileNotFoundError(p)
    try:
        with np.load(p) as z:
            names = set(z.files)
            for name in sorted(names):
                if name.startswith(_CRC_PREFIX):
                    continue
                crc_key = _CRC_PREFIX + name
                if crc_key not in names:
                    continue
                want = int(z[crc_key])
                got = _crc32(z[name])
                if got != want:
                    raise CheckpointCorruptError(
                        f"{p}: array {name!r} CRC mismatch "
                        f"(stored {want:#010x}, computed {got:#010x})")
    except CheckpointCorruptError:
        raise
    except Exception as e:   # noqa: BLE001 — zip/zlib/pickle damage
        raise CheckpointCorruptError(f"{p}: unreadable npz: {e!r}") from e


def _gen_path(dst: str, n: int) -> str:
    """Retained-generation name: ``ckpt.npz`` -> ``ckpt.g<n>.npz`` (must
    keep the .npz suffix so npz_path() round-trips the name)."""
    return f"{dst[:-len('.npz')]}.g{n}.npz"


def _gen_files(dst: str) -> List[int]:
    """Existing generation numbers for ``dst``, ascending."""
    stem = glob.escape(dst[:-len(".npz")])
    gens = []
    for p in glob.glob(stem + ".g*.npz"):
        tail = p[len(dst) - len(".npz") + 2:-len(".npz")]
        try:
            gens.append(int(tail))
        except ValueError:
            continue
    return sorted(gens)


def rotate_before_write(dst: str, retain: int) -> None:
    """Retention step 1, called right before an atomic overwrite of
    ``dst``: rename the current live checkpoint to the next generation
    (``ckpt.g<n>.npz``) so the overwrite cannot destroy the only valid
    copy.  No-op for ``retain <= 1`` or when ``dst`` does not exist."""
    if retain <= 1 or not os.path.exists(dst):
        return
    gens = _gen_files(dst)
    os.replace(dst, _gen_path(dst, (gens[-1] + 1) if gens else 1))


def prune_generations(dst: str, retain: int) -> None:
    """Retention step 2, called after a successful write: drop all but
    the newest ``retain - 1`` generations (live file + k-1 gens = k)."""
    if retain <= 1:
        return
    for n in _gen_files(dst)[:-(retain - 1)]:
        try:
            os.unlink(_gen_path(dst, n))
        except OSError:
            pass


def find_latest_valid_checkpoint(path: str) -> Optional[str]:
    """Newest checkpoint for ``path`` that passes CRC validation: the
    live file if valid, else retained generations newest-first (written
    by ``save_checkpoint(..., retain=k)``).  Returns a loadable path or
    None.  Corrupt candidates are logged and skipped — this is the
    fallback scan ``train_with_resume`` rewinds through."""
    from swiftmpi_tpu.utils.logger import get_logger
    log = get_logger(__name__)
    dst = npz_path(path)
    candidates = [dst] + [_gen_path(dst, n)
                          for n in reversed(_gen_files(dst))]
    for cand in candidates:
        if not os.path.exists(cand):
            continue
        try:
            verify_checkpoint(cand)
            return cand
        except CheckpointCorruptError as e:
            log.warning("skipping corrupt checkpoint %s: %s", cand, e)
    return None


def save_checkpoint(table: SparseTable, path: str,
                    extra: Optional[Dict[str, np.ndarray]] = None,
                    retain: int = 1) -> None:
    """npz with all fields (incl. optimizer state), the key index, and any
    extra arrays (e.g. step counters) — resume-exact, unlike the reference
    text dump which drops h2sum/v2sum (word2vec.h:100-110).

    ``retain > 1`` keeps a last-k window: before the atomic replace, the
    previous live checkpoint is renamed to ``<path>.g<n>.npz`` and
    generations beyond ``retain - 1`` are pruned — so a checkpoint that
    lands corrupted (torn write, bit rot, injected fault) still leaves
    ``find_latest_valid_checkpoint`` an older valid file to rewind to."""
    with obs.span("checkpoint_save"):
        keys = np.fromiter(table.key_index.keys(), dtype=np.uint64,
                           count=len(table.key_index))
        slots = np.fromiter((table.key_index.slot(int(k)) for k in keys),
                            dtype=np.int64, count=len(keys))
        payload = {}
        for f, v in table.state.items():
            arr = host_array(v)
            if arr.dtype.name == "bfloat16":
                # np.savez has no bfloat16: it round-trips as raw '|V2' and
                # load explodes.  fp32 is an exact superset of bf16, so
                # upcast here and cast back at load — bit-identical.
                arr = arr.astype(np.float32)
            payload[f"field__{f}"] = arr
        payload["keys"] = keys
        payload["slots"] = slots
        payload["num_shards"] = np.int64(table.key_index.num_shards)
        payload["capacity_per_shard"] = np.int64(
            table.key_index.capacity_per_shard)
        # hybrid placement: the hot-head size travels with the checkpoint so
        # load can refuse a table built under a different frequency split
        # (the @hot field arrays are in the field__ payload like any other)
        payload["n_hot"] = np.int64(table.n_hot)
        for k, v in (extra or {}).items():
            payload[f"extra__{k}"] = np.asarray(v)
        if not is_writer():        # gather above was the collective part
            return
        dst = npz_path(path)
        rotate_before_write(dst, retain)
        # atomic: a crash mid-write must never clobber the last good
        # checkpoint (it is the only thing auto-resume can rewind to)
        atomic_savez(dst, payload)
        prune_generations(dst, retain)
        reg = obs.get_registry()
        if reg.enabled:
            reg.counter("checkpoint/saves").inc()


def load_checkpoint(table: SparseTable, path: str,
                    verify: bool = True) -> Dict[str, np.ndarray]:
    """Restore table state + key index from ``save_checkpoint`` output;
    returns the ``extra`` arrays.  ``verify`` (default on) CRC-validates
    every array first and raises :class:`CheckpointCorruptError` instead
    of silently restoring damaged state — callers with a retention window
    catch it and rewind via ``find_latest_valid_checkpoint``."""
    with obs.span("checkpoint_restore"):
        extra = _load_checkpoint(table, path, verify)
    reg = obs.get_registry()
    if reg.enabled:
        reg.counter("checkpoint/restores").inc()
    return extra


def _load_checkpoint(table: SparseTable, path: str,
                     verify: bool) -> Dict[str, np.ndarray]:
    if verify:
        verify_checkpoint(path)
    with np.load(npz_path(path)) as z:
        if int(z["num_shards"]) != table.key_index.num_shards:
            raise ValueError(
                f"checkpoint has {int(z['num_shards'])} shards, table has "
                f"{table.key_index.num_shards}")
        saved_cap = int(z["capacity_per_shard"])
        if saved_cap > table.key_index.capacity_per_shard:
            # checkpoint written after SparseTable.grow(): adopt its
            # capacity (the text path auto-grows for the same case; only
            # shrink remains an error).  Bookkeeping only — state arrays
            # and the index are overwritten from the npz just below, so
            # SparseTable.grow()'s device-side remap (which transiently
            # doubles HBM use) would be wasted work.
            table.key_index.grow(saved_cap)
        elif saved_cap < table.key_index.capacity_per_shard:
            raise ValueError(
                f"checkpoint capacity_per_shard {saved_cap} is smaller "
                f"than the table's {table.key_index.capacity_per_shard}; "
                "shrinking on load is not supported")
        saved_hot = int(z["n_hot"]) if "n_hot" in z.files else 0
        if saved_hot != table.n_hot:
            raise ValueError(
                f"checkpoint has n_hot={saved_hot}, table has "
                f"n_hot={table.n_hot} — the hot/cold partition is fixed "
                "at vocab build; rebuild the model under the same "
                "frequency split before restoring")
        saved_ef = {zname[len("field__"):] for zname in z.files
                    if zname.startswith("field__")
                    and is_ef_field(zname[len("field__"):])}
        table_ef = set(table.ef_fields)
        if saved_ef != table_ef:
            # a silent mismatch either drops pending residuals (EF
            # checkpoint into a quant-off run: unapplied gradient mass
            # vanishes) or zero-seeds planes mid-stream (non-EF
            # checkpoint into an EF run: fine mathematically but almost
            # always a misconfigured resume) — refuse loudly either way
            raise ValueError(
                f"checkpoint EF residual planes {sorted(saved_ef)} do "
                f"not match the table's {sorted(table_ef)} — restore "
                "with the same [cluster] wire_quant setting the "
                "checkpoint was written under (or rebuild the model "
                "with matching error-feedback arming)")
        state = {}
        for zname in z.files:
            if not zname.startswith("field__"):
                continue
            name = zname[len("field__"):]
            arr = z[zname]
            if is_ef_field(name) or name == ROWVER_KEY:
                # EF residual planes (f32) and the @rowver version
                # plane (int32) are not access fields — no FieldSpec,
                # no dtype cast.  Restoring @rowver as saved keeps
                # versions counting up across restarts, so a resumed
                # worker's cold cache can never collide with a re-used
                # stamp (pull_cache.py invalidation contract).
                state[name] = _replace(table, name, arr)
                continue
            # @hot arrays restore next to their base field with the same
            # storage dtype (and their replicated placement, via
            # _replace's per-name sharding)
            fs = table.access.fields[base_field(name)]
            if arr.dtype != fs.dtype:
                # bf16 fields were saved upcast to fp32 (npz has no
                # bfloat16); restore the table's storage dtype exactly
                arr = arr.astype(fs.dtype)
            if arr.shape[1] < fs.dim:
                # written before rows were stored wider than they are
                # (access.stored_width): the new lanes are zero
                arr = np.pad(arr, ((0, 0), (0, fs.dim - arr.shape[1])))
            state[name] = _replace(table, name, arr)
        had_rowver = ROWVER_KEY in table.state
        table.state = state
        table.key_index.restore(z["keys"], z["slots"])
        if had_rowver and ROWVER_KEY not in state:
            # pre-delta-pull checkpoint into a pull_cache-armed table:
            # re-arm a zero plane (version 0 = "never applied") rather
            # than silently dropping the cache for the rest of the run.
            # Safe — the resume path flushes every worker shadow, so
            # the reset stamps cannot false-hit.
            table.ensure_row_versions()
        return {k[len("extra__"):]: z[k] for k in z.files
                if k.startswith("extra__")}
