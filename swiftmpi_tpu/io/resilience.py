"""Failure recovery: elastic checkpoint reshard + supervised auto-resume.

The reference has none of this — its hashfrag header says "without
Replication, Fault Tolerance and Repair" (`/root/reference/src/cluster/
hashfrag.h:13`) and a dead node hangs the pull/push barrier forever
(SURVEY.md §5).  On an SPMD TPU deployment the failure model is different: a
chip/host failure kills the whole program, so recovery means *restart from
checkpoint* — these utilities make that path first-class:

* ``load_checkpoint_elastic`` — restore a full-fidelity npz checkpoint into
  a table with a **different shard count / capacity** (scale the mesh up or
  down between runs).  The strict ``load_checkpoint`` refuses mismatched
  geometry because exact resume must be bit-stable; the elastic variant
  re-keys every row through the new table's KeyIndex instead.
* ``train_with_resume`` — wrap a model's train loop with
  checkpoint-every-k-iterations and automatic reload-and-retry on failure
  (bounded restarts), turning the mid-training checkpoints
  (io/checkpoint.py) into actual fault tolerance.  Resumes pick the newest
  checkpoint that passes CRC validation (a corrupted latest falls back to
  an older retained generation), failures can optionally trigger a device
  health sweep (utils/health.py), and a hang watchdog bounds the time an
  attempt may go without step progress — a stuck collective becomes a
  checkpoint-restart instead of an infinite wait.

Chaos scenarios are injected through ``testing/faults.py``: pass a
``FaultPlan`` and the crash/hang/corruption you want to survive happens
deterministically inside the wrapped training run.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

import numpy as np

from swiftmpi_tpu.cluster.bootstrap import host_array
from swiftmpi_tpu.io.checkpoint import (_replace,
                                        find_latest_valid_checkpoint,
                                        npz_path, save_checkpoint,
                                        verify_checkpoint)
from swiftmpi_tpu.parameter.sparse_table import SparseTable
from swiftmpi_tpu.testing import faults
from swiftmpi_tpu.utils.health import DeviceHangError, check_devices
from swiftmpi_tpu.utils.logger import get_logger

log = get_logger(__name__)


def load_checkpoint_elastic(table: SparseTable, path: str,
                            verify: bool = True) -> Dict[str, np.ndarray]:
    """Restore an npz checkpoint into a table whose shard geometry may
    differ from the checkpoint's: every key is re-routed through the new
    table's KeyIndex (new hashfrag, new slot ranges) and its row moved to
    the new slot.  Optimizer state travels with the row, so training
    continues exactly (up to row placement) after a mesh resize.

    ``verify`` CRC-validates the file first (CheckpointCorruptError on
    damage) — an elastic restore is usually a recovery action, exactly
    when silently loading bit-rot would hurt most.

    Returns the checkpoint's ``extra`` arrays (e.g. the iteration counter).
    Raises ``CapacityError`` if the new geometry cannot hold all rows.
    """
    if verify:
        verify_checkpoint(path)
    with np.load(npz_path(path)) as z:
        keys = z["keys"]
        old_slots = z["slots"]
        new_slots = np.asarray(table.key_index.lookup(keys), np.int64)
        state = dict(table.state)
        for name in table.access.fields:
            # host_array, not np.asarray: state may be a non-fully-
            # addressable global array in multi-process runs
            arr = host_array(state[name]).copy()
            saved = z[f"field__{name}"]       # may predate the wider
            arr[new_slots, :saved.shape[1]] = saved[old_slots]  # stored row
            state[name] = _replace(table, name, arr)
        table.state = state
        log.info("elastic restore: %d rows re-keyed from %d-shard "
                 "checkpoint into %d-shard table", len(keys),
                 int(z["num_shards"]), table.key_index.num_shards)
        return {k[len("extra__"):]: z[k] for k in z.files
                if k.startswith("extra__")}


class _AttemptAbandoned(Exception):
    """Raised inside an abandoned attempt thread (via the fault-bus
    observer) at its next step event, so a watchdog-cancelled trainer
    stops instead of racing the restarted one for the model state."""


def _attempt(model, call_kwargs: dict, hang_timeout_s: Optional[float],
             probe_timeout_s: float):
    """One training attempt.  Without a hang timeout this is just
    ``model.train(**call_kwargs)``.  With one, the attempt runs on a
    worker thread while this thread watches the fault-bus heartbeat
    (every ``step_event`` from the training loop beats it); silence
    longer than ``hang_timeout_s`` triggers a device health sweep
    (utils/health.py) and a ``DeviceHangError``.  The stalled worker is
    cancelled cooperatively — its next step event raises — and must
    acknowledge within a grace period; if it never does (a truly wedged
    native call), the error is marked non-recoverable so the caller
    escalates to a process restart (the supervised launcher's job)
    instead of racing a zombie trainer for the model state."""
    if not hang_timeout_s:
        return model.train(**call_kwargs)

    result: dict = {}
    beat = {"t": time.monotonic()}
    cancel = threading.Event()

    def obs(event, payload):
        beat["t"] = time.monotonic()
        if cancel.is_set():
            raise _AttemptAbandoned("attempt cancelled by hang watchdog")

    def worker():
        try:
            result["losses"] = model.train(**call_kwargs)
        except BaseException as e:  # noqa: BLE001 — relayed to caller
            result["error"] = e

    faults.add_observer(obs)
    t = threading.Thread(target=worker, daemon=True,
                         name="train-attempt")
    t.start()
    try:
        while t.is_alive():
            t.join(0.05)
            if not t.is_alive():
                break
            stalled = time.monotonic() - beat["t"]
            if stalled <= hang_timeout_s:
                continue
            # no step progress within the deadline: classify via device
            # probes, cancel the attempt, and hand the failure to the
            # resume loop as a restartable error
            report = check_devices(timeout_s=probe_timeout_s)
            bad = [(h.device, h.error) for h in report if not h.ok]
            cancel.set()
            grace = max(hang_timeout_s, 5.0)
            t.join(grace)
            recoverable = not t.is_alive()
            msg = (f"no training progress for {stalled:.1f}s "
                   f"(deadline {hang_timeout_s:.1f}s); "
                   + (f"unhealthy devices: {bad}" if bad
                      else "device probes healthy (stalled host loop)"))
            if not recoverable:
                msg += ("; attempt thread did not acknowledge "
                        f"cancellation within {grace:.0f}s — escalate to "
                        "process restart")
            err = DeviceHangError(msg)
            err.recoverable = recoverable
            raise err
    finally:
        faults.remove_observer(obs)
    if "error" in result:
        err = result["error"]
        if isinstance(err, _AttemptAbandoned):
            # the worker acked a cancellation raised AFTER it already
            # finished hanging — the watchdog error was raised instead
            raise DeviceHangError("attempt cancelled by hang watchdog")
        raise err
    return result["losses"]


def train_with_resume(model, data=None, niters: int = 1,
                      checkpoint_path: str = "ckpt",
                      checkpoint_every: int = 1,
                      max_restarts: int = 2,
                      batcher=None,
                      retain: int = 2,
                      fault_plan: Optional[faults.FaultPlan] = None,
                      probe_devices: bool = False,
                      probe_timeout_s: float = 30.0,
                      hang_timeout_s: Optional[float] = None,
                      **train_kwargs):
    """Run ``model.train`` to ``niters`` total iterations with periodic
    checkpoints, resuming from the latest *valid* checkpoint after a
    failure (up to ``max_restarts`` times).  If a checkpoint already
    exists at ``checkpoint_path``, training continues from it — so
    re-running the same command after a crash (the SPMD failure model:
    the process dies) also picks up where it left off.

    The model must provide ``train(..., checkpoint_path,
    checkpoint_every, checkpoint_retain)`` and ``resume(path) ->
    start_iter`` (Word2Vec does).  Returns the per-iteration losses of
    the final successful ``train`` call, i.e. of iterations
    ``start..niters`` (failed attempts' partial losses are lost with the
    exception; a resumed run reports only the iterations it ran).

    Robustness knobs:

    * ``retain`` — checkpoint generations kept on disk (last-k window).
      Every resume scans newest-to-oldest for the first file that passes
      CRC validation, so a corrupted latest checkpoint rewinds one
      generation instead of aborting the run.
    * ``fault_plan`` — a ``testing.faults.FaultPlan`` installed for the
      duration of the call: chaos (crash at step k, hang, checkpoint
      corruption) becomes a reproducible test instead of a manual poke.
    * ``probe_devices`` — after every failure, sweep the device mesh
      with bounded health probes and log the verdict before retrying.
    * ``hang_timeout_s`` — watchdog deadline on step progress; a stalled
      attempt (hung device, stuck collective) is detected, probed, and
      restarted from checkpoint instead of waiting forever.
    """
    installed_plan = None
    if fault_plan is not None:
        installed_plan = faults.install(fault_plan)
    try:
        start = 0
        best = find_latest_valid_checkpoint(checkpoint_path)
        if best is not None:
            start = int(model.resume(best))
            log.info("found valid checkpoint %s at iter %d; continuing",
                     best, start)
        elif getattr(model, "table", None) is not None:
            # iter-0 snapshot: a crash before the first periodic
            # checkpoint must rewind to the true initial state, not
            # retrain on top of partially-updated rows
            save_checkpoint(model.table, checkpoint_path,
                            extra={"iter": np.int64(0)}, retain=retain)
        restarts = 0
        losses = []
        while True:
            remaining = niters - start
            if remaining <= 0:
                return losses
            call_kwargs = dict(
                data=data, niters=remaining,
                checkpoint_path=checkpoint_path,
                checkpoint_every=checkpoint_every,
                checkpoint_retain=retain, start_iter=start,
                batcher=batcher, **train_kwargs)
            try:
                losses = _attempt(model, call_kwargs, hang_timeout_s,
                                  probe_timeout_s)
                return losses
            except Exception as e:  # noqa: BLE001 — retry any failure
                if isinstance(e, DeviceHangError) and \
                        not getattr(e, "recoverable", True):
                    log.error("unrecoverable hang — escalating to the "
                              "process supervisor: %s", e)
                    raise
                restarts += 1
                if restarts > max_restarts:
                    log.error("giving up after %d restarts: %s",
                              max_restarts, e)
                    raise
                if probe_devices and not isinstance(e, DeviceHangError):
                    # hang path already probed; probe organic failures
                    # too so the log shows WHAT died, not just that
                    # something did
                    report = check_devices(timeout_s=probe_timeout_s)
                    bad = [(h.device, h.error)
                           for h in report if not h.ok]
                    if bad:
                        log.warning("post-failure probe: unhealthy "
                                    "devices %s", bad)
                best = find_latest_valid_checkpoint(checkpoint_path)
                if best is None:
                    # no valid checkpoint to rewind to (table was not
                    # built before the crash, or every generation is
                    # corrupt) — retrying would train on mutated state
                    log.error("no valid checkpoint to rewind to; "
                              "re-raising")
                    raise
                start = int(model.resume(best))
                log.warning("training failed (%s); restart %d/%d from "
                            "iter %d (%s)", e, restarts, max_restarts,
                            start, best)
    finally:
        if installed_plan is not None:
            faults.install(None)
