#!/usr/bin/env python
"""Fleet-smoke worker: a telemetry-armed step loop for launch.py drills.

The 4-process fleet smoke (scripts/fleet_smoke.py, tests/test_fleet.py)
needs children that exercise the whole per-rank telemetry surface —
step records, spans, wire counters, heartbeats, fault injection — while
needing NOTHING cross-process: no jax.distributed init, no collectives.
That keeps the smoke's capability probe down to "can this container
spawn subprocesses", instead of the much rarer "do cross-process
collectives work here".

Each rank runs ``SMTPU_FLEET_STEPS`` steps of ``SMTPU_FLEET_STEP_S``
seconds of (slept) dispatch work, booking rank-skewed wire traffic —
rank r books ``1000 * (r + 1)`` bytes/step, so the fleet's
``wire_bytes_imbalance`` is deterministic and nonzero — and calls the
fault bus at the top of every step, which is where a launcher-installed
``SMTPU_FAULT_PLAN`` (hang / kill drills) fires.  Telemetry lands in
``SMTPU_FLEET_DIR`` (obs.configure's fleet redirect); heartbeat cadence
comes from ``SMTPU_FLEET_HB_S``.

``SMTPU_FLEET_NUMERICS=1`` additionally arms the numerics health plane
(obs/numerics.py) with synthetic per-rank gradient norms and a live
AnomalyDetector, so the fleet merge carries ``numerics/*`` gauges and
anomaly events end to end without any real training;
``SMTPU_FLEET_NUMERICS_SPIKE=<step>`` injects a 40x grad-norm spike on
``SMTPU_FLEET_NUMERICS_SPIKE_RANK`` (default 0) at that step — the
drill that must surface as an anomaly in the member table.

``SMTPU_FLEET_TRACE=1`` arms the wire tracer (obs/trace.py) and drives
it with one synthetic coalesced window per step through the SAME feed
API the transfer ledgers use (priced decision, key reservoir, dedup,
rank-skewed exchange), so the flight-recorder drill — rank 0 drops a
``trace_trigger.json`` mid-run, every rank's tracer replays it into a
``trace_r<rank>_p<pid>.jsonl`` dump in the fleet dir — runs end to end
without any real transfer backend.

``SMTPU_ELASTIC=1`` switches the step loop to an
:class:`~swiftmpi_tpu.cluster.elastic.ElasticWorker` under
``launch.py -elastic 1``'s member table (ISSUE 16): the child boots
into the published membership, syncs it at the top of every step (the
safe point — adoptions, two-phase rejoins, and rollbacks all land
here), trains its owned rows, and publishes ``elastic/epoch`` /
``elastic/loss`` / ``elastic/rows_owned`` gauges plus
``elastic/migration_bytes`` and modeled ``transfer/wire_bytes``
counters, so the FleetCollector's epoch/reconvergence/imbalance view
works off the ordinary telemetry streams.  ``SMTPU_ELASTIC_SHARDS`` /
``_ROWS`` / ``_DIM`` / ``_DUMP_EVERY`` size the workload; a rank
evicted by a rollback re-enters through ``boot()``.  A rank trains on
past ``SMTPU_FLEET_STEPS`` while the world is not whole (a peer dead or
mid-rejoin) or its committed epoch is below the one the fault plan ends
on (two a planned kill), at most the join timeout longer.  Prints
``ELASTIC_CHILD_OK rank=<r> steps=<n> epoch=<e> loss=<l>`` on a clean
finish; a stale-epoch rejection exits rc 3 (loud, never silent).

Prints ``FLEET_CHILD_OK rank=<r> steps=<n>`` on a clean finish.
"""

from __future__ import annotations

import itertools
import os
import sys
import time

# launched as `python scripts/_fleet_child.py`: sys.path[0] is scripts/,
# so the package root must be added by hand
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from swiftmpi_tpu import obs                          # noqa: E402
from swiftmpi_tpu.testing import faults              # noqa: E402
from swiftmpi_tpu.utils.config import ConfigParser   # noqa: E402


def elastic_main(rec, reg, rank: int, steps: int, step_s: float,
                 fleet_dir: str) -> int:
    """Elastic step loop: ElasticWorker under the supervisor-owned
    member table (see module docstring)."""
    from swiftmpi_tpu.cluster.bootstrap import ENV_NUM_PROCESSES
    from swiftmpi_tpu.cluster.elastic import ElasticWorker
    from swiftmpi_tpu.cluster.membership import COMMITTED, StaleEpochError

    world = int(os.environ.get(ENV_NUM_PROCESSES, "1"))
    worker = ElasticWorker(
        rank, fleet_dir, world_size=world,
        n_shards=int(os.environ.get("SMTPU_ELASTIC_SHARDS",
                                    str(4 * world))),
        rows_per_shard=int(os.environ.get("SMTPU_ELASTIC_ROWS", "32")),
        dim=int(os.environ.get("SMTPU_ELASTIC_DIM", "8")),
        dump_every=int(os.environ.get("SMTPU_ELASTIC_DUMP_EVERY", "5")))
    join_timeout = float(os.environ.get("SMTPU_ELASTIC_JOIN_TIMEOUT_S",
                                        "30"))
    # the epoch the drill's faults end on: each planned kill costs two,
    # the death's repartition and the rejoin's commit
    plan = faults.active()
    leave_epoch = 2 * sum(f.kind == "kill" for f in plan.faults) \
        if plan else 0
    row_bytes = 4 + worker.dim * 4
    booked_mig = 0
    loss = 0.0
    try:
        if not worker.boot(timeout_s=join_timeout):
            print(f"elastic_child: rank {rank} never admitted within "
                  f"{join_timeout}s", file=sys.stderr)
            return 4
        # a rank leaves a WHOLE world only, and not before the epoch the
        # drill ends on: while a peer is still to be killed, or is on
        # its way back (restart backoff + boot + two-phase handback),
        # the others keep training past ``steps`` — a rank that left
        # could never ack the rejoin's prepare, and how long a start or
        # a restart takes is the host's business, not the drill's.  The
        # ceiling is the join timeout, past which the peer was abandoned.
        def whole() -> bool:
            t = worker.member_table
            return (t is not None and t.state == COMMITTED
                    and len(t.live) == world and t.epoch >= leave_epoch)

        leave_by = None
        for step in itertools.count():
            if step >= steps:
                leave_by = leave_by or time.monotonic() + join_timeout
                if whole() or time.monotonic() >= leave_by:
                    break
            faults.step_event(step)       # kill/hang drills fire here
            events = worker.sync()        # the safe point
            if any(e.get("kind") == "evicted" for e in events):
                if not worker.boot(timeout_s=join_timeout):
                    print(f"elastic_child: rank {rank} evicted and "
                          "never re-admitted", file=sys.stderr)
                    return 4
            with obs.span("dispatch"):
                loss = worker.step()
                time.sleep(step_s)
            reg.gauge("elastic/epoch").set(float(worker.epoch))
            reg.gauge("elastic/loss").set(float(loss))
            reg.gauge("elastic/rows_owned").set(float(len(worker.rows)))
            if worker.migration_bytes > booked_mig:
                reg.counter("elastic/migration_bytes").inc(
                    worker.migration_bytes - booked_mig)
                booked_mig = worker.migration_bytes
            # modeled per-step training wire: owned rows x sparse row
            # bytes — what feeds the fleet_wire_bytes_imbalance gate
            reg.counter("transfer/wire_bytes", backend="elastic").inc(
                len(worker.rows) * row_bytes)
            reg.counter("transfer/dispatches", backend="elastic").inc(1)
            obs.record_step(1)
    except StaleEpochError as e:
        print(f"elastic_child: STALE EPOCH on rank {rank}: {e}",
              file=sys.stderr)
        return 3
    worker.write_census()
    rec.close()
    print(f"ELASTIC_CHILD_OK rank={rank} steps={step} "
          f"epoch={worker.epoch} loss={loss:.6f}")
    return 0


def main() -> int:
    steps = int(os.environ.get("SMTPU_FLEET_STEPS", "60"))
    step_s = float(os.environ.get("SMTPU_FLEET_STEP_S", "0.02"))
    hb_s = float(os.environ.get("SMTPU_FLEET_HB_S", "0.25"))
    fleet_dir = os.environ.get("SMTPU_FLEET_DIR", "")
    trace = os.environ.get("SMTPU_FLEET_TRACE", "0") not in ("", "0")

    obs_cfg = {"heartbeat_s": hb_s}
    if trace:
        # dumps land next to the telemetry streams so the smoke (and
        # smtpu_top/telemetry_report --trace) find them in one place
        obs_cfg.update({"trace": 1, "trace_dir": fleet_dir or "runs"})
    cfg = ConfigParser().update({
        "worker": {"telemetry": 1},
        "obs": obs_cfg,
    })
    rec = obs.configure(cfg, run="fleet_child")
    if rec is None:
        print("fleet_child: telemetry failed to arm", file=sys.stderr)
        return 2
    rank = obs.process_rank() or 0
    reg = obs.get_registry()

    if os.environ.get("SMTPU_ELASTIC", "0") not in ("", "0"):
        return elastic_main(rec, reg, rank, steps, step_s, fleet_dir)

    tr = obs.get_tracer()
    if tr is not None:
        # one pricing per compiled program, the decide_wire_format way:
        # sparse wins, the losing candidates' modeled byte costs ride
        # along as the record's "why"
        tr.on_decision("xla", "sparse",
                       {"dense": 8192.0, "sparse": 2048.0,
                        "sparse_q": 1152.0, "bitmap": 1536.0},
                       rows=32, capacity=128, row_bytes=64,
                       quant="int8")

    det = None
    spike_at = spike_rank = -1
    if os.environ.get("SMTPU_FLEET_NUMERICS", "0") not in ("", "0"):
        from swiftmpi_tpu.obs import numerics as obs_numerics
        det = obs_numerics.AnomalyDetector()
        spike_at = int(os.environ.get("SMTPU_FLEET_NUMERICS_SPIKE",
                                      "-1"))
        spike_rank = int(os.environ.get(
            "SMTPU_FLEET_NUMERICS_SPIKE_RANK", "0"))

    for step in range(steps):
        faults.step_event(step)         # hang/kill drills fire here
        with obs.span("dispatch"):
            time.sleep(step_s)
        reg.counter("transfer/wire_bytes",
                    backend="xla").inc(1000 * (rank + 1))
        reg.counter("transfer/dispatches", backend="xla").inc(1)
        reg.counter("transfer/window_fmt", backend="xla",
                    fmt="sparse").inc(1)
        if tr is not None:
            # one synthetic window per step, rank-skewed like the wire
            # counter above (rows x row_bytes = 1000 * (rank + 1))
            tr.stage_keys("xla", [(rank + 1) * k for k in range(8)])
            tr.on_window("xla", "sparse", rows_in=48, rows_out=32)
            tr.on_exchange("xla", rows=250 * (rank + 1), row_bytes=4)
            if rank == 0 and step == steps // 2 and fleet_dir:
                # the operator flow: drop the fleet-wide dump trigger
                # (same file the `python -m swiftmpi_tpu.obs.trace`
                # CLI writes); every rank replays it exactly once
                from swiftmpi_tpu.obs import trace as trace_mod
                trace_mod.request_trace(fleet_dir)
        if det is not None:
            # deterministic per-rank norms (mild skew, below the
            # cross-rank divergence factor) + optional injected spike
            g = 1.0 + 0.1 * rank
            if step == spike_at and rank == spike_rank:
                g *= 40.0
            loss = 2.0 / (1.0 + 0.05 * step)
            reg.gauge("numerics/grad_norm").set(g)
            reg.gauge("numerics/loss").set(loss)
            det.on_sample(reg, {"numerics/grad_norm": g,
                                "numerics/loss": loss}, 0.0)
        obs.record_step(1)

    if tr is not None and fleet_dir:
        # grace window: the trigger poll is throttled (poll_s), so a
        # trigger dropped near the end of a short drill may not have
        # been seen yet — keep polling (no step advance) until the dump
        # lands or the grace expires
        deadline = time.time() + 3.0
        while not tr.dumps and time.time() < deadline:
            tr.on_step(0)
            time.sleep(0.1)
        # clean teardown: detach WITHOUT dumping, so a normal exit does
        # not overwrite the trigger dump with a crash dump
        obs.uninstall_tracer()

    rec.close()
    print(f"FLEET_CHILD_OK rank={rank} steps={steps}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
