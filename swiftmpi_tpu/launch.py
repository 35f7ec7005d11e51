"""Single-host multi-process launcher: the ``mpirun -np N`` equivalent.

The reference is launched as ``mpirun -np N -hostfile hosts ./bin/word2vec
-config ... -data ...`` (`/root/reference/src/apps/word2vec/cluster_run.sh:2`,
``run.sh`` for the single-process variant).  Here::

    python -m swiftmpi_tpu.launch -np 4 -- python -m \
        swiftmpi_tpu.apps.w2v_main -config demo.conf -data corpus.txt ...

spawns N local processes wired to one ``jax.distributed`` coordinator (the
bootstrap env contract in cluster/bootstrap.py); each child calls
``init_distributed()`` via ``Cluster.initialize()`` and sees the global
device set.  Multi-host launches are the pod scheduler's job — it sets the
same three env vars per host; this launcher is the dev/CI story, exactly
like the reference's loopback ``mpirun -np 1`` (SURVEY.md §4).

Flags (reference CMDLine style, ``-key value``):

* ``-np N``       — number of processes (default 1).
* ``-cpu D``      — give each process D virtual CPU devices
                    (JAX_PLATFORMS=cpu + xla_force_host_platform_device_count;
                    the standard fake-multi-device trick for development).
                    Required for more than one process unless the
                    environment already pins ``JAX_PLATFORMS=cpu``: the
                    launcher assigns no chips to ranks, and one process
                    drives all chips of a host through the mesh.
* ``-port P``     — coordinator port (default: an OS-assigned free port).
* ``-max-restarts R`` — supervised mode: on any non-zero world exit,
                    restart ALL ranks from scratch up to R times with
                    exponential backoff (the SPMD recovery model:
                    restart-the-world, resume from checkpoint — pair
                    with ``train_with_resume`` in the child).
* ``-backoff S``  — initial restart backoff seconds (default 1.0,
                    doubling per restart, capped at 60s).
* ``-stable-after S`` — reset the restart-attempt budget after the
                    world (or, elastic mode, the rank) ran S seconds
                    before failing: ``max_restarts`` bounds crash-LOOPS,
                    not the total organic hiccups of a long run.
* ``-elastic 1``  — per-rank failure domains (ISSUE 16): one rank dying
                    is repartitioned across survivors and restarted
                    alone instead of tearing the world down.  See
                    :func:`supervise_elastic`; requires ``-fleet-dir``.
                    ``-shards K``, ``-join-timeout S``, ``-dead-after S``
                    tune the member table, rejoin deadline, and
                    hung-rank detection.
* ``-serve N``    — serve-fleet mode (ISSUE 17): rank 0 is the trainer,
                    ranks 1..N are replica readers replaying the
                    delta-shipped snapshot stream from ``-ship-dir``
                    (default ``<fleet-dir>/ship``).  Replica restarts
                    ride the per-rank budgets; a dead trainer leaves
                    the replicas serving stale-but-bounded.
                    ``-trainer-restarts R`` budgets the trainer
                    separately.  Requires ``-fleet-dir``.
* ``-fleet-dir D`` — arm fleet observability (ISSUE 12): children get
                    ``SMTPU_FLEET_DIR=D`` (their StepRecorder writes
                    per-rank heartbeat'd JSONL streams there, see
                    obs.configure) and the launcher appends its own
                    ``smtpu-fleet-sup/1`` events — spawn/exit with
                    normalized rc and a ``by_supervisor`` flag that
                    separates organic deaths from teardown kills,
                    restart, world_start/world_exit — to
                    ``D/supervisor.jsonl``, so a FleetCollector can
                    correlate a rank's silence with *why* it went
                    silent.
* ``-profile-at N`` — pre-arm a triggered profiler window on EVERY
                    rank: children get ``SMTPU_PROFILE_AT=N`` and each
                    rank's ProfileSession (obs/profiler.py) captures a
                    bounded ``jax.profiler`` trace when its consumed-
                    step count reaches N.  For a live run, use
                    ``python -m swiftmpi_tpu.obs.profiler <fleet_dir>``
                    instead — the trigger file reaches running ranks.
* ``-profile-steps K`` — capture window length for ``-profile-at``
                    (``SMTPU_PROFILE_STEPS``; default 5).

Children inherit stdout/stderr with a ``[rank k]`` line prefix; first
non-zero exit terminates the rest (mpirun semantics): survivors get
SIGTERM, then SIGKILL after a grace period, every child is reaped, and
readers are drained before ``launch`` returns — no leaked processes, no
orphaned output pumps.  Exit codes propagate to ``main()``'s return;
signal deaths map to the shell convention ``128 + signum``.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from swiftmpi_tpu.cluster.bootstrap import (ENV_COORDINATOR,
                                            ENV_FLEET_DIR,
                                            ENV_NUM_PROCESSES,
                                            ENV_PROCESS_ID)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env(base: Dict[str, str], port: int, rank: int, nprocs: int,
               cpu_devices: int,
               fleet_dir: Optional[str] = None) -> Dict[str, str]:
    env = dict(base)
    env[ENV_COORDINATOR] = f"127.0.0.1:{port}"
    env[ENV_NUM_PROCESSES] = str(nprocs)
    if fleet_dir:
        env[ENV_FLEET_DIR] = fleet_dir
    # besides the jax.distributed rank, ENV_PROCESS_ID is the process
    # identity every log line and telemetry record carries ("r<rank>",
    # obs/identity.py) — interleaved supervisor output and per-rank
    # telemetry.jsonl stay attributable after the fact
    env[ENV_PROCESS_ID] = str(rank)
    if cpu_devices:
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform_"
                                     "device_count")]
        flags.append(
            f"--xla_force_host_platform_device_count={cpu_devices}")
        env["XLA_FLAGS"] = " ".join(flags)
    return env


def _normalize_rc(code: int) -> int:
    """Child exit code -> process exit code.  Popen reports signal
    deaths as negative numbers; ``sys.exit(-9)`` would wrap to an
    arbitrary byte at the OS boundary, so map them to the shell
    convention 128 + signum (SIGKILL -> 137)."""
    return 128 - code if code < 0 else code


def launch(argv: List[str], nprocs: int, cpu_devices: int = 0,
           port: int = 0, kill_grace_s: float = 5.0,
           fleet_dir: Optional[str] = None, fleet_log=None,
           attempt: int = 0) -> int:
    """Spawn ``nprocs`` copies of ``argv`` under one coordinator; returns
    the first non-zero child exit code (terminating the others), else 0.

    One reader thread per child (a blocking ``readline`` there cannot
    stall exit detection here); the main thread only polls exit codes.
    SIGTERM on first failure escalates to SIGKILL after ``kill_grace_s``.
    Teardown order is kill -> reap -> drain -> join: every child is
    ``wait``-ed (no zombies), and a reader blocked on a pipe a grandchild
    still holds is unblocked by force-closing the pipe, not abandoned
    mid-pump.

    ``fleet_log`` (a :class:`~swiftmpi_tpu.obs.collector.SupervisorLog`,
    owned by :func:`supervise` so it spans restarts) receives one
    ``spawn`` per Popen and exactly one ``exit`` per child —
    ``by_supervisor`` distinguishes ranks this teardown killed from the
    rank that died on its own, which is what lets a FleetCollector
    attribute the world failure to the right member.
    """
    port = port or _free_port()
    if fleet_dir and fleet_log is None:
        from swiftmpi_tpu.obs.collector import SupervisorLog
        fleet_log = SupervisorLog(fleet_dir)
    procs = []
    print_lock = threading.Lock()
    exited: Dict[int, int] = {}        # rank -> raw code, logged once
    terminated: set = set()            # ranks we delivered a signal to

    def note_exit(rank: int, p) -> None:
        code = p.poll()
        if fleet_log is None or code is None or rank in exited:
            return
        exited[rank] = code
        fleet_log.event("exit", rank=rank, pid=p.pid,
                        rc=_normalize_rc(code),
                        by_supervisor=rank in terminated,
                        attempt=attempt)

    def reader(rank: int, stream) -> None:
        try:
            for line in stream:                  # until EOF
                with print_lock:
                    sys.stdout.write(f"[rank {rank}] {line}")
                    sys.stdout.flush()
        except (ValueError, OSError):
            pass     # stream force-closed by teardown while blocked

    threads = []
    for rank in range(nprocs):
        p = subprocess.Popen(
            argv, env=_child_env(os.environ, port, rank, nprocs,
                                 cpu_devices, fleet_dir),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        procs.append(p)
        if fleet_log is not None:
            fleet_log.event("spawn", rank=rank, pid=p.pid,
                            attempt=attempt)
        t = threading.Thread(target=reader, args=(rank, p.stdout),
                             daemon=True)
        t.start()
        threads.append(t)

    rc = 0
    try:
        while any(p.poll() is None for p in procs):
            time.sleep(0.1)
            for i, p in enumerate(procs):
                code = p.poll()
                if code is not None:
                    note_exit(i, p)    # organic exit: log BEFORE any
                                       # teardown marks ranks terminated
                if code not in (None, 0) and rc == 0:
                    rc = _normalize_rc(code)   # first failure wins
                    for j, q in enumerate(procs):
                        if q.poll() is None:
                            terminated.add(j)
                            q.terminate()
                    deadline = time.monotonic() + kill_grace_s
                    for j, q in enumerate(procs):
                        try:
                            q.wait(max(0.0, deadline - time.monotonic()))
                        except subprocess.TimeoutExpired:
                            q.kill()   # SIGTERM ignored: escalate
                        note_exit(j, q)
        for i, p in enumerate(procs):
            code = p.wait()
            note_exit(i, p)
            if code and rc == 0:
                rc = _normalize_rc(code)
    finally:
        # kill: nothing may survive this function, success or raise
        for i, p in enumerate(procs):
            if p.poll() is None:
                terminated.add(i)
                p.kill()
        # reap: every kill needs a wait or the child stays a zombie (the
        # old teardown skipped this — `ps` after a failed launch showed
        # defunct ranks until the launcher itself exited)
        for i, p in enumerate(procs):
            try:
                p.wait(timeout=kill_grace_s)
            except subprocess.TimeoutExpired:
                pass               # unkillable (D-state); nothing to do
            note_exit(i, p)
        # drain: child death EOFs the pipe, so readers normally finish
        # on their own...
        for t in threads:
            t.join(timeout=2.0)
        # ...unless a grandchild inherited the pipe's write end and kept
        # it open — then force-close the read end to unblock the reader
        # (it swallows the resulting ValueError/OSError) and join again
        for p, t in zip(procs, threads):
            if t.is_alive():
                try:
                    p.stdout.close()
                except (ValueError, OSError):
                    pass
        for t in threads:
            t.join(timeout=1.0)
    return rc


def supervise(argv: List[str], nprocs: int, cpu_devices: int = 0,
              port: int = 0, kill_grace_s: float = 5.0,
              max_restarts: int = 0, backoff_s: float = 1.0,
              backoff_factor: float = 2.0,
              backoff_max_s: float = 60.0,
              fleet_dir: Optional[str] = None,
              stable_after_s: Optional[float] = None) -> int:
    """Restart-the-world supervisor around :func:`launch`.

    The SPMD recovery model (io/resilience.py): a failed rank cannot be
    patched back into a running world — the barrier is already poisoned
    — so ANY non-zero world exit tears everything down and relaunches
    all ranks, which resume from the last valid checkpoint when the
    child uses ``train_with_resume``.  Restarts are bounded
    (``max_restarts``) with exponential backoff so a deterministic
    crash-loop exhausts its budget and surfaces the real exit code
    instead of flapping forever.  With the default ``port=0`` every
    attempt picks a fresh coordinator port — the previous coordinator's
    socket may linger in TIME_WAIT.

    With ``fleet_dir``, ONE SupervisorLog spans every attempt — restart
    events land between the attempts' spawn/exit runs, so the collector
    sees a rank's pre- and post-restart lives as one member history.

    ``stable_after_s`` resets the restart-attempt counter after the
    world has run that long before failing: a week-long run with an
    occasional recoverable crash should not exhaust ``max_restarts``
    budgeted for crash-LOOPS and give up on its Nth organic hiccup —
    only failures in quick succession burn the budget."""
    attempt = 0
    fleet_log = None
    if fleet_dir:
        from swiftmpi_tpu.obs.collector import SupervisorLog
        fleet_log = SupervisorLog(fleet_dir)
        fleet_log.event("world_start", nprocs=nprocs,
                        max_restarts=max_restarts, argv=list(argv))
    try:
        while True:
            t_start = time.monotonic()
            rc = launch(argv, nprocs, cpu_devices, port, kill_grace_s,
                        fleet_dir=fleet_dir, fleet_log=fleet_log,
                        attempt=attempt)
            ran_s = time.monotonic() - t_start
            if rc != 0 and attempt and stable_after_s is not None \
                    and ran_s >= stable_after_s:
                print(f"[launch] world was stable {ran_s:.1f}s >= "
                      f"{stable_after_s:.1f}s; restart budget reset",
                      file=sys.stderr)
                if fleet_log is not None:
                    fleet_log.event("stable_reset", ran_s=ran_s,
                                    attempt=attempt)
                attempt = 0
            if rc == 0:
                if attempt:
                    print(f"[launch] world recovered after {attempt} "
                          f"restart(s)", file=sys.stderr)
                if fleet_log is not None:
                    fleet_log.event("world_exit", rc=0, attempt=attempt)
                return 0
            if attempt >= max_restarts:
                if max_restarts:
                    print(f"[launch] restart budget exhausted "
                          f"({max_restarts}); giving up with rc={rc}",
                          file=sys.stderr)
                if fleet_log is not None:
                    fleet_log.event("world_exit", rc=rc, attempt=attempt)
                return rc
            delay = min(backoff_s * (backoff_factor ** attempt),
                        backoff_max_s)
            attempt += 1
            print(f"[launch] world failed rc={rc}; restart "
                  f"{attempt}/{max_restarts} in {delay:.1f}s",
                  file=sys.stderr)
            if fleet_log is not None:
                fleet_log.event("restart", rc=rc, attempt=attempt,
                                delay_s=delay)
            time.sleep(delay)
    finally:
        if fleet_log is not None:
            fleet_log.close()


def _publish_epoch(fleet_dir: str, table, fleet_log, reason: str) -> None:
    """The supervisor's ONLY membership-write path: publish a new member
    table and put the epoch transition on the fleet timeline in the same
    breath, so the collector can correlate every ownership change with
    the supervisor evidence that caused it."""
    from swiftmpi_tpu.cluster import membership as mem
    # epoch-guard: mem.write_membership validates the epoch advance
    # (same-epoch rewrites other than prepare->commit raise
    # StaleEpochError) — this helper exists so every supervisor-side
    # table write goes through that check exactly once
    mem.write_membership(fleet_dir, table)
    if fleet_log is not None:
        fleet_log.event("epoch", epoch=table.epoch, state=table.state,
                        live=list(table.live), reason=reason,
                        moves=len(table.moves))


def _shard_weights(fleet_dir: str, n_shards: int) -> List[float]:
    """Fleet-wide per-shard load: sum of every rank's published
    DecayedSketch fold (cluster.membership.publish_load); shards nobody
    reported weigh 1.0 so placement degrades to balance-by-count."""
    from swiftmpi_tpu.cluster import membership as mem
    total = [0.0] * n_shards
    for vec in mem.read_loads(fleet_dir, n_shards).values():
        for s, v in enumerate(vec):
            total[s] += float(v)
    return [v if v > 0 else 1.0 for v in total]


def _handback_shards(table, weight: List[float], k: int) -> List[int]:
    """Pick ``k`` shards to hand back to a rejoining rank: repeatedly
    take the heaviest shard from the currently most-loaded survivor —
    the inverse of the death-path LPT, so a rejoin UNDOES imbalance
    instead of adding to it."""
    owned = {r: sorted(table.shards_of(r), key=lambda s: -weight[s])
             for r in table.live}
    load = {r: sum(weight[s] for s in owned[r]) for r in table.live}
    picks: List[int] = []
    for _ in range(max(k, 0)):
        donors = [r for r in table.live if len(owned[r]) > 1]
        if not donors:       # never strip a survivor's last shard
            break
        r = max(donors, key=lambda r: (load[r], -r))
        s = owned[r].pop(0)
        load[r] -= weight[s]
        picks.append(s)
    return picks


def supervise_elastic(argv: List[str], nprocs: int, *, fleet_dir: str,
                      cpu_devices: int = 0, port: int = 0,
                      kill_grace_s: float = 5.0, max_restarts: int = 2,
                      backoff_s: float = 0.5, backoff_factor: float = 2.0,
                      backoff_max_s: float = 30.0,
                      stable_after_s: Optional[float] = None,
                      join_timeout_s: float = 20.0,
                      n_shards: Optional[int] = None,
                      dead_after_s: Optional[float] = None,
                      poll_s: float = 0.1) -> int:
    """Per-rank failure domains: the elastic alternative to
    :func:`supervise`'s restart-the-world.

    The supervisor owns the member table (cluster/membership.py) and is
    its only writer.  One rank dying does NOT tear the world down:

    1. the exit is reaped and logged (normalized rc, ``by_supervisor``);
    2. if a two-phase rejoin was in flight, it is rolled back first
       (``plan_death`` refuses to operate over a PREPARE table — the
       all-or-nothing rule);
    3. the dead rank's shards are repartitioned across survivors with
       :func:`~swiftmpi_tpu.control.controller.plan_placement` — the
       Controller's Parallax rule over the ranks' published
       DecayedSketch folds — and the new COMMITTED epoch is published;
       survivors adopt the orphans from the dead rank's last dump
       (staleness <= its dump cadence);
    4. the rank is restarted with per-RANK backoff (``stable_after_s``
       resets a rank's attempt budget after a long stable run) and
       re-admitted through the two-phase prepare/commit rejoin when its
       join request arrives — or abandoned once its budget is spent,
       with the world carrying on minus one failure domain.

    ``dead_after_s`` arms the detection half the exit code cannot see:
    a HUNG rank (alive, silent) is judged by FleetCollector health
    against the wall clock and killed, which routes it into the same
    death path.  Requires the children to heartbeat via
    ``SMTPU_FLEET_DIR`` telemetry.

    Returns 0 when every rank finished rc=0; else the first abandoned
    rank's rc (the world ran degraded but is still reported honestly).
    """
    from swiftmpi_tpu.cluster import membership as mem
    from swiftmpi_tpu.obs.collector import SupervisorLog

    os.makedirs(fleet_dir, exist_ok=True)
    n_shards = n_shards or 4 * nprocs
    port = port or _free_port()
    table = mem.initial_table(nprocs, n_shards)
    fleet_log = SupervisorLog(fleet_dir)
    fleet_log.event("world_start", nprocs=nprocs, mode="elastic",
                    n_shards=n_shards, max_restarts=max_restarts,
                    argv=list(argv))
    _publish_epoch(fleet_dir, table, fleet_log, "init")

    print_lock = threading.Lock()
    procs: Dict[int, subprocess.Popen] = {}
    threads: List[threading.Thread] = []
    attempts: Dict[int, int] = {r: 0 for r in range(nprocs)}
    last_start: Dict[int, float] = {}
    restart_due: Dict[int, float] = {}
    finished: set = set()
    abandoned: set = set()
    terminated: set = set()            # ranks we delivered a signal to
    prepare_deadline: Optional[float] = None
    last_health_poll = 0.0
    rc_final = 0

    def reader(rank: int, stream) -> None:
        try:
            for line in stream:
                with print_lock:
                    sys.stdout.write(f"[rank {rank}] {line}")
                    sys.stdout.flush()
        except (ValueError, OSError):
            pass

    def spawn(rank: int) -> None:
        p = subprocess.Popen(
            argv, env=_child_env(os.environ, port, rank, nprocs,
                                 cpu_devices, fleet_dir),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        procs[rank] = p
        last_start[rank] = time.monotonic()
        fleet_log.event("spawn", rank=rank, pid=p.pid,
                        attempt=attempts[rank])
        t = threading.Thread(target=reader, args=(rank, p.stdout),
                             daemon=True)
        t.start()
        threads.append(t)

    def note_exit(rank: int, p, code: int) -> None:
        fleet_log.event("exit", rank=rank, pid=p.pid,
                        rc=_normalize_rc(code),
                        by_supervisor=rank in terminated,
                        attempt=attempts[rank])
        terminated.discard(rank)

    def handle_death(rank: int) -> None:
        """Membership half of a rank failure: rollback any in-flight
        prepare, then repartition the dead rank's shards across the
        survivors and publish the new epoch."""
        nonlocal table, prepare_deadline
        if table.state == mem.PREPARE:
            table = mem.rollback_table(
                table, reason=f"rank {rank} died mid-prepare")
            _publish_epoch(fleet_dir, table, fleet_log, table.reason)
            prepare_deadline = None
        if rank not in table.live:
            return                   # was the rolled-back rejoiner
        if len(table.live) == 1:
            # last live rank: nobody to repartition onto — its restart
            # resumes from its own dump, a world-of-one restart
            return
        from swiftmpi_tpu.control.controller import plan_placement
        dead_shards = table.shards_of(rank)
        survivors = [r for r in table.live if r != rank]
        assign = plan_placement(dead_shards, survivors,
                                mem.read_loads(fleet_dir, n_shards),
                                table.owner_of_shard)
        table = mem.plan_death(table, rank, assign)
        _publish_epoch(fleet_dir, table, fleet_log, table.reason)

    for rank in range(nprocs):
        spawn(rank)
    try:
        while procs or restart_due:
            now = time.monotonic()
            # 1. reap exits — each a per-rank failure domain
            for rank, p in list(procs.items()):
                code = p.poll()
                if code is None:
                    continue
                note_exit(rank, p, code)
                del procs[rank]
                if code == 0:
                    finished.add(rank)
                    continue
                if stable_after_s is not None and attempts[rank] \
                        and now - last_start[rank] >= stable_after_s:
                    fleet_log.event("stable_reset", rank=rank,
                                    ran_s=now - last_start[rank],
                                    attempt=attempts[rank])
                    attempts[rank] = 0
                handle_death(rank)
                if attempts[rank] >= max_restarts:
                    rcn = _normalize_rc(code)
                    print(f"[launch] rank {rank} out of restart budget "
                          f"({max_restarts}); abandoned rc={rcn}",
                          file=sys.stderr)
                    fleet_log.event("rank_abandoned", rank=rank, rc=rcn)
                    abandoned.add(rank)
                    rc_final = rc_final or rcn
                else:
                    delay = min(backoff_s * (backoff_factor
                                             ** attempts[rank]),
                                backoff_max_s)
                    attempts[rank] += 1
                    fleet_log.event("restart_rank", rank=rank,
                                    rc=_normalize_rc(code),
                                    attempt=attempts[rank],
                                    delay_s=delay)
                    restart_due[rank] = now + delay
            # 2. spawn due restarts (they re-enter via a join request)
            for rank, due in list(restart_due.items()):
                if now >= due:
                    del restart_due[rank]
                    spawn(rank)
            # 3. drive an in-flight prepare to commit or rollback
            if table.state == mem.PREPARE:
                if mem.acks_complete(fleet_dir, table):
                    table = mem.commit_table(table)
                    _publish_epoch(fleet_dir, table, fleet_log,
                                   "commit: " + table.reason)
                    prepare_deadline = None
                elif prepare_deadline is not None \
                        and now >= prepare_deadline:
                    table = mem.rollback_table(table,
                                               reason="prepare timeout")
                    _publish_epoch(fleet_dir, table, fleet_log,
                                   table.reason)
                    prepare_deadline = None
            # 4. admit pending joins (only from a committed table)
            elif table.state == mem.COMMITTED:
                for rank, claimed in sorted(
                        mem.pending_joins(fleet_dir).items()):
                    if rank in table.live:
                        continue
                    verdict = mem.judge_join(table, rank, claimed)
                    if verdict == "stale":
                        mem.write_reject(
                            fleet_dir, rank,
                            reason=f"claimed epoch {claimed} is ahead "
                                   f"of the world's {table.epoch}")
                        mem.clear_join(fleet_dir, rank)
                        fleet_log.event("join_rejected", rank=rank,
                                        claimed=claimed,
                                        epoch=table.epoch)
                        continue
                    weight = _shard_weights(fleet_dir, n_shards)
                    share = n_shards // (len(table.live) + 1)
                    picks = _handback_shards(table, weight, share)
                    assign = {s: rank for s in picks}
                    table = mem.plan_rejoin(table, rank, assign)
                    _publish_epoch(fleet_dir, table, fleet_log,
                                   table.reason)
                    prepare_deadline = time.monotonic() + join_timeout_s
                    break          # one prepare in flight at a time
            # 5. hung-rank detection: alive but silent past dead_after_s
            if dead_after_s and now - last_health_poll >= 1.0:
                last_health_poll = now
                from swiftmpi_tpu.obs.collector import FleetCollector
                coll = FleetCollector(fleet_dir, dead_after_s=dead_after_s)
                coll.poll()
                for key, status in coll.health(at=time.time()).items():
                    try:
                        hrank = int(key.lstrip("r"))
                    except ValueError:
                        continue
                    p = procs.get(hrank)
                    if status == "dead" and p is not None \
                            and p.poll() is None:
                        print(f"[launch] rank {hrank} hung (silent > "
                              f"{dead_after_s:.1f}s); killing",
                              file=sys.stderr)
                        fleet_log.event("hang_kill", rank=hrank,
                                        pid=p.pid)
                        terminated.add(hrank)
                        p.kill()
            time.sleep(poll_s)
        fleet_log.event("world_exit", rc=rc_final,
                        finished=sorted(finished),
                        abandoned=sorted(abandoned))
        return rc_final
    finally:
        for rank, p in procs.items():
            if p.poll() is None:
                terminated.add(rank)
                p.kill()
        for rank, p in procs.items():
            try:
                p.wait(timeout=kill_grace_s)
            except subprocess.TimeoutExpired:
                pass
            note_exit(rank, p, p.poll() if p.poll() is not None else -9)
        for t in threads:
            t.join(timeout=2.0)
        for rank, p in procs.items():
            try:
                p.stdout.close()
            except (ValueError, OSError):
                pass
        for t in threads:
            t.join(timeout=1.0)
        fleet_log.close()


#: role env var the serve-fleet children read: "trainer" or "replica"
ENV_SERVE_ROLE = "SMTPU_SERVE_ROLE"
#: snapshot ship directory (serve/shipper.py stream) for both roles
ENV_SHIP_DIR = "SMTPU_SHIP_DIR"


def supervise_serve(argv: List[str], n_replicas: int, *, fleet_dir: str,
                    ship_dir: Optional[str] = None,
                    cpu_devices: int = 0, port: int = 0,
                    kill_grace_s: float = 5.0, max_restarts: int = 2,
                    trainer_restarts: Optional[int] = None,
                    backoff_s: float = 0.5, backoff_factor: float = 2.0,
                    backoff_max_s: float = 30.0,
                    stable_after_s: Optional[float] = None,
                    poll_s: float = 0.1) -> int:
    """Serve-fleet supervisor (ISSUE 17): one trainer rank + N replica
    reader ranks over a shared snapshot-ship directory.

    Failure domains are per-rank, riding the PR-16 budget machinery,
    but the roles are asymmetric in exactly the way serving wants:

    * a **replica** dying takes zero write-path capacity with it — it
      restarts alone under its per-rank backoff budget and re-syncs by
      replaying the newest full base + deltas from the ship dir (the
      version chain IS the recovery path; no peer coordination);
    * the **trainer** dying does NOT tear the replicas down: they keep
      serving the last shipped version — stale but bounded, with the
      replica-side ``serve/staleness_s`` gauge rising — while the
      trainer restarts (its shipper resumes the version stream past
      the manifest tail, forced full) or is abandoned.

    Ranks: 0 = trainer, 1..N = replicas; children learn their role via
    ``SMTPU_SERVE_ROLE`` and the stream location via ``SMTPU_SHIP_DIR``
    (default ``<fleet_dir>/ship``).  Returns 0 when every rank finished
    rc=0, else the first abandoned rank's rc.
    """
    from swiftmpi_tpu.obs.collector import SupervisorLog

    nprocs = n_replicas + 1
    os.makedirs(fleet_dir, exist_ok=True)
    ship_dir = ship_dir or os.path.join(fleet_dir, "ship")
    os.makedirs(ship_dir, exist_ok=True)
    port = port or _free_port()
    if trainer_restarts is None:
        trainer_restarts = max_restarts
    fleet_log = SupervisorLog(fleet_dir)
    fleet_log.event("world_start", nprocs=nprocs, mode="serve_fleet",
                    n_replicas=n_replicas, ship_dir=ship_dir,
                    max_restarts=max_restarts,
                    trainer_restarts=trainer_restarts, argv=list(argv))

    def role_of(rank: int) -> str:
        return "trainer" if rank == 0 else "replica"

    def budget_of(rank: int) -> int:
        return trainer_restarts if rank == 0 else max_restarts

    print_lock = threading.Lock()
    procs: Dict[int, subprocess.Popen] = {}
    threads: List[threading.Thread] = []
    attempts: Dict[int, int] = {r: 0 for r in range(nprocs)}
    last_start: Dict[int, float] = {}
    restart_due: Dict[int, float] = {}
    finished: set = set()
    abandoned: set = set()
    terminated: set = set()
    rc_final = 0

    def reader(rank: int, stream) -> None:
        try:
            for line in stream:
                with print_lock:
                    sys.stdout.write(f"[rank {rank}] {line}")
                    sys.stdout.flush()
        except (ValueError, OSError):
            pass

    def spawn(rank: int) -> None:
        env = _child_env(os.environ, port, rank, nprocs, cpu_devices,
                         fleet_dir)
        env[ENV_SERVE_ROLE] = role_of(rank)
        env[ENV_SHIP_DIR] = ship_dir
        p = subprocess.Popen(argv, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT, text=True)
        procs[rank] = p
        last_start[rank] = time.monotonic()
        fleet_log.event("spawn", rank=rank, pid=p.pid,
                        role=role_of(rank), attempt=attempts[rank])
        t = threading.Thread(target=reader, args=(rank, p.stdout),
                             daemon=True)
        t.start()
        threads.append(t)

    def note_exit(rank: int, p, code: int) -> None:
        fleet_log.event("exit", rank=rank, pid=p.pid,
                        rc=_normalize_rc(code), role=role_of(rank),
                        by_supervisor=rank in terminated,
                        attempt=attempts[rank])
        terminated.discard(rank)

    for rank in range(nprocs):
        spawn(rank)
    try:
        while procs or restart_due:
            now = time.monotonic()
            for rank, p in list(procs.items()):
                code = p.poll()
                if code is None:
                    continue
                note_exit(rank, p, code)
                del procs[rank]
                if code == 0:
                    finished.add(rank)
                    continue
                if stable_after_s is not None and attempts[rank] \
                        and now - last_start[rank] >= stable_after_s:
                    fleet_log.event("stable_reset", rank=rank,
                                    ran_s=now - last_start[rank],
                                    attempt=attempts[rank])
                    attempts[rank] = 0
                if attempts[rank] >= budget_of(rank):
                    rcn = _normalize_rc(code)
                    print(f"[launch] serve {role_of(rank)} rank {rank} "
                          f"out of restart budget ({budget_of(rank)}); "
                          f"abandoned rc={rcn}", file=sys.stderr)
                    fleet_log.event("rank_abandoned", rank=rank,
                                    role=role_of(rank), rc=rcn)
                    abandoned.add(rank)
                    rc_final = rc_final or rcn
                else:
                    delay = min(backoff_s * (backoff_factor
                                             ** attempts[rank]),
                                backoff_max_s)
                    attempts[rank] += 1
                    fleet_log.event("restart_rank", rank=rank,
                                    role=role_of(rank),
                                    rc=_normalize_rc(code),
                                    attempt=attempts[rank],
                                    delay_s=delay)
                    restart_due[rank] = now + delay
            for rank, due in list(restart_due.items()):
                if now >= due:
                    del restart_due[rank]
                    spawn(rank)
            time.sleep(poll_s)
        fleet_log.event("world_exit", rc=rc_final,
                        finished=sorted(finished),
                        abandoned=sorted(abandoned))
        return rc_final
    finally:
        for rank, p in procs.items():
            if p.poll() is None:
                terminated.add(rank)
                p.kill()
        for rank, p in procs.items():
            try:
                p.wait(timeout=kill_grace_s)
            except subprocess.TimeoutExpired:
                pass
            note_exit(rank, p, p.poll() if p.poll() is not None else -9)
        for t in threads:
            t.join(timeout=2.0)
        for rank, p in procs.items():
            try:
                p.stdout.close()
            except (ValueError, OSError):
                pass
        for t in threads:
            t.join(timeout=1.0)
        fleet_log.close()


def main(args: Optional[List[str]] = None) -> int:
    from swiftmpi_tpu.utils.cmdline import CMDLine

    if args is None:
        args = sys.argv[1:]
    if "--" not in args:
        print("usage: python -m swiftmpi_tpu.launch -np N [-cpu D] "
              "[-port P] -- prog args...", file=sys.stderr)
        return 2
    split = args.index("--")
    cmd = CMDLine(["launch"] + args[:split])
    cmd.registerParameter("np", "number of processes")
    cmd.registerParameter("cpu", "virtual CPU devices per process")
    cmd.registerParameter("port", "coordinator port")
    cmd.registerParameter("max-restarts",
                          "restart-the-world budget on failure")
    cmd.registerParameter("backoff", "initial restart backoff seconds")
    cmd.registerParameter("stable-after",
                          "reset restart budget after this many stable "
                          "seconds")
    cmd.registerParameter("elastic",
                          "1 = per-rank failure domains (ISSUE 16): "
                          "restart-the-rank + cross-process "
                          "repartition; requires -fleet-dir")
    cmd.registerParameter("shards",
                          "elastic member-table shard count "
                          "(default 4*np)")
    cmd.registerParameter("join-timeout",
                          "elastic rejoin prepare->commit deadline "
                          "seconds")
    cmd.registerParameter("dead-after",
                          "elastic hung-rank detection: kill a rank "
                          "silent this many seconds")
    cmd.registerParameter("serve",
                          "serve-fleet mode (ISSUE 17): N replica "
                          "reader ranks beside one trainer rank; "
                          "requires -fleet-dir")
    cmd.registerParameter("ship-dir",
                          "snapshot ship directory (default "
                          "<fleet-dir>/ship)")
    cmd.registerParameter("trainer-restarts",
                          "serve-fleet trainer restart budget "
                          "(default: -max-restarts)")
    cmd.registerParameter("fleet-dir",
                          "fleet telemetry directory (ISSUE 12)")
    cmd.registerParameter("profile-at",
                          "pre-arm a profiler capture at step N on "
                          "every rank (ISSUE 14)")
    cmd.registerParameter("profile-steps",
                          "profiler capture window length")
    prog = args[split + 1:]
    if not prog:
        print("launch: nothing to run after --", file=sys.stderr)
        return 2
    # profiler pre-arm rides the inherited environment: _child_env
    # copies os.environ, so every rank of every restart attempt sees it
    from swiftmpi_tpu.obs import profiler as obs_profiler
    if cmd.hasParameter("profile-at"):
        os.environ[obs_profiler.ENV_PROFILE_AT] = str(
            int(cmd.get_value("profile-at")))
    if cmd.hasParameter("profile-steps"):
        os.environ[obs_profiler.ENV_PROFILE_STEPS] = str(
            int(cmd.get_value("profile-steps")))
    nprocs = int(cmd.get_value("np")) if cmd.hasParameter("np") else 1
    cpu = int(cmd.get_value("cpu")) if cmd.hasParameter("cpu") else 0
    fleet_dir = (cmd.get_value("fleet-dir")
                 if cmd.hasParameter("fleet-dir") else None)
    stable_after_s = (float(cmd.get_value("stable-after"))
                      if cmd.hasParameter("stable-after") else None)
    n_serve = int(cmd.get_value("serve")) if cmd.hasParameter("serve") else 0
    if ((nprocs > 1 or n_serve) and not cpu
            and os.environ.get("JAX_PLATFORMS") != "cpu"):
        # a chip belongs to one process and ranks get no per-rank chip
        # assignment: each would claim every accelerator of the host
        print("launch: more than one process without -cpu D would let "
              "every rank claim every accelerator of this host (the "
              "launcher assigns no chips to ranks).  One process drives "
              "all chips of a host through the mesh: run the program "
              "directly, or pass -cpu D (or JAX_PLATFORMS=cpu) for a "
              "CPU world.", file=sys.stderr)
        return 2
    if n_serve:
        if not fleet_dir:
            print("launch: -serve requires -fleet-dir (the supervisor "
                  "log and ship stream live there)", file=sys.stderr)
            return 2
        return supervise_serve(
            prog, n_serve, fleet_dir=fleet_dir,
            ship_dir=(cmd.get_value("ship-dir")
                      if cmd.hasParameter("ship-dir") else None),
            cpu_devices=cpu,
            port=int(cmd.get_value("port"))
            if cmd.hasParameter("port") else 0,
            max_restarts=int(cmd.get_value("max-restarts"))
            if cmd.hasParameter("max-restarts") else 2,
            trainer_restarts=int(cmd.get_value("trainer-restarts"))
            if cmd.hasParameter("trainer-restarts") else None,
            backoff_s=float(cmd.get_value("backoff"))
            if cmd.hasParameter("backoff") else 0.5,
            stable_after_s=stable_after_s)
    if cmd.hasParameter("elastic") and int(cmd.get_value("elastic")):
        if not fleet_dir:
            print("launch: -elastic requires -fleet-dir (the member "
                  "table and migration deltas live there)",
                  file=sys.stderr)
            return 2
        return supervise_elastic(
            prog, nprocs, fleet_dir=fleet_dir, cpu_devices=cpu,
            port=int(cmd.get_value("port"))
            if cmd.hasParameter("port") else 0,
            max_restarts=int(cmd.get_value("max-restarts"))
            if cmd.hasParameter("max-restarts") else 2,
            backoff_s=float(cmd.get_value("backoff"))
            if cmd.hasParameter("backoff") else 0.5,
            stable_after_s=stable_after_s,
            join_timeout_s=float(cmd.get_value("join-timeout"))
            if cmd.hasParameter("join-timeout") else 20.0,
            n_shards=int(cmd.get_value("shards"))
            if cmd.hasParameter("shards") else None,
            dead_after_s=float(cmd.get_value("dead-after"))
            if cmd.hasParameter("dead-after") else None)
    return supervise(
        prog,
        nprocs=nprocs,
        cpu_devices=cpu,
        port=int(cmd.get_value("port")) if cmd.hasParameter("port") else 0,
        max_restarts=int(cmd.get_value("max-restarts"))
        if cmd.hasParameter("max-restarts") else 0,
        backoff_s=float(cmd.get_value("backoff"))
        if cmd.hasParameter("backoff") else 1.0,
        fleet_dir=fleet_dir,
        stable_after_s=stable_after_s)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
