"""Fleet observability (ISSUE 12): FleetCollector merge/health/skew,
heartbeats + flush-on-crash in StepRecorder, supervisor event
correlation, restart identity, crashed-stream repair, and the end-to-end
4-process chaos drill (injected stall -> straggler attribution; SIGTERM
kill -> live->dead with the supervisor exit correlated).

The multiprocess pieces run REAL subprocesses under swiftmpi_tpu.launch
and need only subprocess spawning (the children never touch
jax.distributed), so the capability probe here is much lighter than
test_multiprocess's collective probe.
"""

import functools
import json
import os
import signal
import subprocess
import sys
import time

import pytest

from swiftmpi_tpu import obs
from swiftmpi_tpu.obs.collector import (FleetCollector, SupervisorLog,
                                        repair_json_line,
                                        stream_filename)
from swiftmpi_tpu.obs.recorder import StepRecorder
from swiftmpi_tpu.utils.config import ConfigParser

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO, "scripts")


# ---------------------------------------------------------------------------
# capability probe: can this container spawn a python child that imports
# the package?  (No collectives involved — the fleet children are
# telemetry loops, not jax.distributed participants.)

@functools.lru_cache(maxsize=1)
def _subprocess_support():
    try:
        r = subprocess.run(
            [sys.executable, "-c", "import swiftmpi_tpu; print('ok')"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": REPO}, cwd=REPO)
    except (OSError, subprocess.TimeoutExpired) as e:
        return False, f"cannot spawn python subprocess: {e}"
    if r.returncode != 0 or "ok" not in r.stdout:
        return False, (f"child import failed rc={r.returncode}: "
                       f"{(r.stderr or r.stdout).strip()[:200]}")
    return True, ""


def require_subprocess():
    ok, reason = _subprocess_support()
    if not ok:
        pytest.skip(f"subprocess spawning unavailable ({reason})")


def _env(extra):
    return {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
            **extra}


# ---------------------------------------------------------------------------
# collector units over synthesized streams (no subprocesses)

def _write_stream(dirpath, rank, pid, t0, steps, step_s=0.1,
                  hb_every=1, wire_per_step=1000, summary=True,
                  truncate_tail=False):
    """Hand-rolled smtpu-telemetry/1 stream with controllable timing."""
    path = os.path.join(dirpath, stream_filename(rank, pid))
    lines = [{"v": 1, "kind": "meta", "schema": "smtpu-telemetry/1",
              "run": "synth", "rank": rank, "pid": pid,
              "ident": f"r{rank}", "ts": t0}]
    t = 0.0
    for i, dt in enumerate(steps, start=1):
        t += dt
        lines.append({"v": 1, "kind": "step", "step": i, "steps": 1,
                      "t": t, "rank": rank, "ident": f"r{rank}",
                      "counters": {"transfer/wire_bytes{backend=xla}":
                                   wire_per_step},
                      "gauges": {}, "hists": {}})
        if hb_every and i % hb_every == 0:
            lines.append({"v": 1, "kind": "heartbeat", "step": i,
                          "t": t, "ts": t0 + t, "rank": rank,
                          "ident": f"r{rank}"})
    if summary:
        lines.append({"v": 1, "kind": "summary", "run": "synth",
                      "rank": rank, "ident": f"r{rank}",
                      "steps": len(steps), "elapsed_s": t,
                      "counters": {}, "gauges": {}, "quantiles": {}})
    blob = "\n".join(json.dumps(ln) for ln in lines) + "\n"
    if truncate_tail:
        blob = blob[:-(len(blob.rsplit("\n", 2)[-2]) // 2 + 1)]
    with open(path, "w") as f:
        f.write(blob)
    return path


def test_collector_merges_and_attributes_straggler(tmp_path):
    d = str(tmp_path)
    t0 = 1000.0
    # rank 1 takes 3x the step time of ranks 0/2 and books 3x the wire
    _write_stream(d, 0, 11, t0, [0.1] * 10)
    _write_stream(d, 1, 12, t0, [0.3] * 10, wire_per_step=3000)
    _write_stream(d, 2, 13, t0, [0.1] * 10)
    fc = FleetCollector(d, stall_after_s=5.0, dead_after_s=15.0)
    fc.poll(final=True)
    s = fc.summary()
    assert s["schema"] == "smtpu-fleet/1"
    assert s["ranks"] == ["0", "1", "2"]
    assert s["straggler_rank"] == "1"
    assert s["straggler_score"] == pytest.approx(3.0, rel=0.05)
    # every aligned interval's slowest member is the straggler
    rows = [r for r in fc.aligned() if "slowest" in r]
    assert rows and all(r["slowest"] == "1" for r in rows)
    # skew: (300 - 100)ms / median 100ms
    assert s["fleet_step_ms_skew_ms"] == pytest.approx(200.0, rel=0.05)
    assert s["fleet_step_ms_skew_pct"] == pytest.approx(200.0, rel=0.1)
    # wire: max 3000/step vs mean (1+3+1)/3 -> 9/5 - 1
    assert s["fleet_wire_bytes_imbalance"] == pytest.approx(0.8,
                                                            rel=0.05)
    assert s["health"] == {"0": "live", "1": "live", "2": "live"}


def test_collector_health_stall_and_dead(tmp_path):
    d = str(tmp_path)
    t0 = 1000.0
    # rank 0: steady to the end; rank 1: an inner 3s gap (stall) then
    # recovers; rank 2: stops at 0.4s and never comes back (dead), with
    # no supervisor log at all -> an UNNOTICED death
    _write_stream(d, 0, 11, t0, [0.1] * 60, summary=False)
    _write_stream(d, 1, 12, t0, [0.1] * 3 + [3.0] + [0.1] * 26,
                  summary=False)
    _write_stream(d, 2, 13, t0, [0.1] * 4, summary=False)
    fc = FleetCollector(d, stall_after_s=1.0, dead_after_s=3.0)
    fc.poll(final=True)
    h = fc.health()          # evaluated at max observed ts (= rank 0's)
    assert h["0"] == "live"
    assert h["1"] == "live"  # recovered: the gap is inner, not trailing
    assert h["2"] == "dead"
    members = fc.members()
    eps = fc.stall_episodes(members["1"])
    assert len(eps) == 1 and eps[0]["gap_s"] == pytest.approx(3.0,
                                                              abs=0.2)
    assert not fc.stall_episodes(members["0"])
    assert fc.unnoticed_deaths() == ["2"]
    # ... and the budget gate hard-fails a candidate carrying that
    fc.write_timeline()
    sys.path.insert(0, SCRIPTS)
    try:
        import check_traffic_budget as ctb
        cells = ctb.load_fleet_cells(os.path.join(d, "fleet.jsonl"))
        (cell,) = cells.values()
        assert cell["unnoticed_deaths"] == 1
        assert ctb.fleet_violations(cells) == [(fc.summary()["run"], 1)]
    finally:
        sys.path.remove(SCRIPTS)


def test_collector_merges_restart_streams_into_one_member(tmp_path):
    """Cross-process identity satellite: same rank, new pid after a
    supervisor restart -> ONE member history with restarts counted and
    both lives' steps present."""
    d = str(tmp_path)
    _write_stream(d, 0, 100, 1000.0, [0.1] * 5, summary=False)   # life 1
    _write_stream(d, 0, 200, 1010.0, [0.1] * 8)                  # life 2
    sup = SupervisorLog(d)
    sup.event("spawn", rank=0, pid=100, attempt=0)
    sup.event("exit", rank=0, pid=100, rc=143, by_supervisor=False,
              attempt=0)
    sup.event("restart", rc=143, attempt=1)
    sup.event("spawn", rank=0, pid=200, attempt=1)
    sup.event("exit", rank=0, pid=200, rc=0, by_supervisor=False,
              attempt=1)
    sup.close()
    fc = FleetCollector(d)
    fc.poll(final=True)
    members = fc.members()
    assert list(members) == ["0"]
    m = members["0"]
    assert m["pids"] == [100, 200]
    assert m["restarts"] == 1
    assert m["records"] == 13            # both lives merged
    assert [e["rc"] for e in m["exits"]] == [143, 0]
    # health keys off the LAST life's exit: rc=0 -> exited, not dead
    assert fc.health()["0"] == "exited"
    assert fc.unnoticed_deaths() == []


def test_collector_repairs_truncated_tail(tmp_path):
    d = str(tmp_path)
    path = _write_stream(d, 0, 11, 1000.0, [0.1] * 6,
                         truncate_tail=True)
    with open(path) as f:
        assert not f.read().endswith("\n")     # genuinely torn
    fc = FleetCollector(d)
    fc.poll(final=True)
    m = fc.members()["0"]
    assert m["recovered"] == 1 and m["dropped"] == 0
    assert m["records"] >= 5


def test_repair_json_line_cases():
    assert repair_json_line(
        '{"v": 1, "kind": "step", "step": 9, "counters": {"a": 1')[
            "step"] == 9
    assert repair_json_line(
        '{"v": 1, "kind": "step", "t": 1.5, "gau')["t"] == 1.5
    assert repair_json_line('{"v": 1, "s": "half string')["v"] == 1
    assert repair_json_line("not json at all") is None


# ---------------------------------------------------------------------------
# recorder: heartbeats + flush-on-crash (in-process)

def test_recorder_heartbeats_flush_immediately(tmp_path):
    path = str(tmp_path / "t.jsonl")
    reg = obs.set_enabled(True)
    rec = StepRecorder(reg, path=path, flush_every=10_000,
                       heartbeat_s=0.01)
    rec.on_steps(1)
    time.sleep(0.02)
    rec.on_steps(1)
    # heartbeat lines must be on disk NOW, not at flush_every/close
    with open(path) as f:
        kinds = [json.loads(ln)["kind"] for ln in f if ln.strip()]
    assert kinds.count("heartbeat") >= 2
    hb = reg.snapshot()["counters"].get("telemetry/heartbeats")
    assert hb and hb >= 2
    rec.close()


def test_fleet_dir_arms_telemetry_and_redirects_stream(tmp_path,
                                                       monkeypatch):
    fleet = tmp_path / "fleet"
    monkeypatch.setenv("SMTPU_FLEET_DIR", str(fleet))
    monkeypatch.setenv("SMTPU_PROCESS_ID", "3")
    # note: NO [worker] telemetry=1 — the fleet dir alone arms it
    rec = obs.configure(ConfigParser(), run="fleet_test")
    assert rec is not None and rec.heartbeat_s == pytest.approx(2.0)
    expected = fleet / stream_filename(3, os.getpid())
    assert rec.path == str(expected)
    rec.on_steps(1)
    rec.close()
    meta = json.loads(expected.read_text().splitlines()[0])
    assert meta["rank"] == 3 and meta["ident"] == "r3"


_CRASH_CHILD = """
import os, signal, sys, time
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from swiftmpi_tpu import obs
from swiftmpi_tpu.obs.recorder import StepRecorder
reg = obs.set_enabled(True)
rec = StepRecorder(reg, path={path!r}, flush_every=10_000,
                   crash_flush=True)
for i in range(100):
    rec.on_steps(1)
    if i == 40:
        print("READY", flush=True)
        time.sleep(30)       # SIGTERM lands here, buffer unflushed
print("UNREACHABLE")
"""


def test_flush_on_crash_sigterm_writes_ring_tail(tmp_path):
    """Satellite 1: kill a child mid-run; the buffered telemetry tail
    (flush_every much larger than the step count) must still reach the
    JSONL, summary included, and the exit code must stay 143."""
    require_subprocess()
    path = str(tmp_path / "crash.jsonl")
    p = subprocess.Popen(
        [sys.executable, "-c",
         _CRASH_CHILD.format(repo=REPO, path=path)],
        stdout=subprocess.PIPE, text=True, env=_env({}))
    try:
        line = p.stdout.readline()
        assert "READY" in line, line
        p.terminate()
        rc = p.wait(timeout=60)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    assert rc in (-signal.SIGTERM, 143)
    with open(path) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    steps = [r["step"] for r in recs if r["kind"] == "step"]
    # 41 steps were consumed before the sleep; nothing was flushed yet
    # (flush_every=10k), so everything on disk is the crash flush's work
    assert steps and max(steps) == 41
    assert recs[-1]["kind"] == "summary"
    assert recs[-1]["steps"] == 41


# ---------------------------------------------------------------------------
# telemetry_report repair + fleet parsing (satellite 3)

def test_telemetry_report_repairs_truncated_final_line(tmp_path,
                                                       capsys):
    path = _write_stream(str(tmp_path), 0, 11, 1000.0, [0.1] * 6,
                         truncate_tail=True)
    sys.path.insert(0, SCRIPTS)
    try:
        import telemetry_report
        doc = telemetry_report.load(path)
    finally:
        sys.path.remove(SCRIPTS)
    assert doc["recovery"] == {"recovered": 1, "dropped": 0}
    rep = telemetry_report.report(doc)
    assert rep["recovery"]["recovered"] == 1


def test_telemetry_report_survives_missing_meta(tmp_path):
    """The truncation that eats the FIRST line: the stream still loads
    (synthesized meta) instead of exiting 2."""
    path = _write_stream(str(tmp_path), 0, 11, 1000.0, [0.1] * 4)
    lines = open(path).read().splitlines()[1:]
    open(path, "w").write("\n".join(lines) + "\n")
    sys.path.insert(0, SCRIPTS)
    try:
        import telemetry_report
        doc = telemetry_report.load(path)
    finally:
        sys.path.remove(SCRIPTS)
    assert doc["meta"].get("synthesized")
    assert len(doc["steps"]) == 4


# ---------------------------------------------------------------------------
# the acceptance drill: 4 real processes, stall + kill chaos

def test_fleet_acceptance_stall_and_kill_drill(tmp_path):
    """ISSUE 12 acceptance: a real launch.py world produces ONE merged
    smtpu-fleet/1 timeline in which (a) the hung rank is the straggler
    with correct attribution, (b) the SIGTERM-killed rank goes
    live->dead with the supervisor exit correlated (rc=143, organic),
    and (c) smtpu_top --once + telemetry_report --fleet both parse it."""
    require_subprocess()
    from swiftmpi_tpu.launch import supervise
    from swiftmpi_tpu.testing.faults import FaultPlan

    fleet = str(tmp_path / "fleet")
    # Drill geometry, ordered by events (marker files), not by how long a
    # rank takes to start on a busy host: rank 2's kill at step 55 waits
    # until rank 1 has recorded its hang (0.8s at step 5) and a few steps
    # after it — the hang step then dominates the common aligned range,
    # making straggler attribution deterministic — and rank 1 waits at
    # step 58 for the kill, then stalls until the teardown sweeps it.
    recorded = str(tmp_path / "hang_recorded")
    killed = str(tmp_path / "rank2_killed")
    plan = (FaultPlan()
            .hang_at_step(5, seconds=0.8, rank=1)
            .hang_at_step(9, seconds=0.0, rank=1, marker=recorded)
            .kill_rank(2, at_step=55, signum=int(signal.SIGTERM),
                       marker=killed, after=recorded)
            .hang_at_step(58, seconds=30.0, rank=1, after=killed))
    os_env = {
        "SMTPU_FAULT_PLAN": plan.to_json(),
        "SMTPU_FLEET_STEPS": "60", "SMTPU_FLEET_STEP_S": "0.02",
        "SMTPU_FLEET_HB_S": "0.2",
    }
    old = {k: os.environ.get(k) for k in os_env}
    os.environ.update(os_env)
    try:
        rc = supervise(
            [sys.executable, os.path.join(SCRIPTS, "_fleet_child.py")],
            nprocs=4, cpu_devices=1, fleet_dir=fleet)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert rc == 143        # rank 2's SIGTERM death, normalized

    fc = FleetCollector(fleet, stall_after_s=0.5, dead_after_s=10.0)
    fc.poll(final=True)
    timeline_path = fc.write_timeline()
    s = fc.summary()
    assert s["ranks"] == ["0", "1", "2", "3"]

    # (a) straggler: the hung rank, by cross-rank total over the common
    # aligned range, with a recorded stall episode
    assert s["straggler_rank"] == "1", s
    assert fc.stall_episodes(fc.members()["1"])

    # (b) the killed rank: dead, with the ORGANIC supervisor exit
    # (by_supervisor=False, rc=143) correlated into its member history
    assert s["health"]["2"] == "dead"
    exits2 = fc.members()["2"]["exits"]
    assert exits2 and exits2[-1]["rc"] == 143
    assert exits2[-1]["by_supervisor"] is False
    # the launcher's teardown kills are attributed AS teardown kills —
    # rank 1 waits for rank 2's death, so it is still running when the
    # teardown sweeps it
    assert any(e["by_supervisor"]
               for e in fc.members()["1"]["exits"])
    # every death is supervised -> the unnoticed-death gate stays quiet
    assert s["unnoticed_deaths"] == []

    # flush-on-crash: rank 2's buffered tail reached its stream — the
    # last recorded step is within a breath of the kill step
    last2 = fc.members()["2"]["last_step"]
    assert last2 is not None and last2 >= 53, last2

    # health transitions in the merged timeline carry the exit evidence
    with open(timeline_path) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    assert recs[0]["schema"] == "smtpu-fleet/1"
    deaths = [r for r in recs if r["kind"] == "health"
              and r.get("to") == "dead" and r["rank"] == "2"]
    assert deaths and deaths[-1]["exit"]["rc"] == 143
    assert not deaths[-1]["unnoticed"]

    # (c) both inspectors parse the artifact
    top = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, "smtpu_top.py"), fleet,
         "--once", "--stall-after", "0.5", "--dead-after", "10"],
        capture_output=True, text=True, timeout=120, env=_env({}))
    assert top.returncode == 0, top.stdout + top.stderr
    assert "STRAGGLER" in top.stdout
    rep = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, "telemetry_report.py"),
         "--fleet", timeline_path],
        capture_output=True, text=True, timeout=120, env=_env({}))
    assert rep.returncode == 0, rep.stdout + rep.stderr
    assert "STRAGGLER: rank 1" in rep.stdout


def test_fleet_restart_identity_across_supervised_restart(tmp_path):
    """Satellite 4, end-to-end: a supervised world where rank 0 crashes
    once (marker-file once-only) and the restart succeeds — the
    collector merges rank 0's two lives (same rank, different pids)
    into one member with restarts=1 and a restart supervisor event."""
    require_subprocess()
    from swiftmpi_tpu.launch import supervise
    from swiftmpi_tpu.testing.faults import FaultPlan

    fleet = str(tmp_path / "fleet")
    marker = str(tmp_path / "crashed_once")
    plan = FaultPlan().kill_rank(0, at_step=5, marker=marker,
                                 signum=int(signal.SIGTERM))
    env = {"SMTPU_FAULT_PLAN": plan.to_json(),
           "SMTPU_FLEET_STEPS": "12", "SMTPU_FLEET_STEP_S": "0.01",
           "SMTPU_FLEET_HB_S": "0.1"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        rc = supervise(
            [sys.executable, os.path.join(SCRIPTS, "_fleet_child.py")],
            nprocs=2, cpu_devices=1, fleet_dir=fleet,
            max_restarts=2, backoff_s=0.1)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert rc == 0          # world recovered on the restart

    fc = FleetCollector(fleet)
    fc.poll(final=True)
    m = fc.members()["0"]
    assert m["restarts"] == 1
    assert len(set(m["pids"])) == 2      # same rank, new pid
    assert m["last_step"] == 12          # the second life finished
    assert fc.health()["0"] == "exited"
    kinds = [e["kind"] for e in fc.supervisor_events]
    assert "restart" in kinds
    assert kinds.count("spawn") == 4     # 2 ranks x 2 attempts
