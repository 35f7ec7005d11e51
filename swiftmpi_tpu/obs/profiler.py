"""Triggered profiler windows: bounded ``jax.profiler`` captures on a
live run (ISSUE 14).

A :class:`ProfileSession` sits on the consumed-step funnel
(``obs.record_step``) and captures an N-step device trace when any of
three triggers fires:

* the ``[obs] profile_at: <step>`` knob (one capture, at that step);
* a trigger file in the fleet directory — ``request_profile()`` / the
  ``python -m swiftmpi_tpu.obs.profiler <fleet_dir>`` CLI writes
  ``profile_trigger.json`` and every rank's session picks it up on its
  next (throttled) poll, so one command profiles the whole fleet
  (``launch.py -profile-at`` pre-arms the same thing via env);
* :meth:`request` — wired to the numerics plane so a critical anomaly
  captures the very steps that misbehaved
  (``[obs] profile_on_anomaly``).

Artifacts land under ``runs/profiles/profile_step<N>_r<rank>/``: the
raw capture (``.xplane.pb`` + the perfetto twin) plus a
``profile_summary.json`` from :func:`parse_trace_dir` — the capture's
``.xplane.pb`` reduced through ``jax.profiler.ProfileData``: device
*self* time (the innermost event owns the instant, so a ``while`` is
not counted over its body) by phase, through the phase map of every
tracked program (``obs.costs.phase_maps``: instruction name -> the
``obs.named_scope`` it was traced under), device time under no phase as
``"unscoped"``, and host time by ``obs.span`` name from the host plane.
``device_ms`` therefore sums to the capture's device busy time.  The
same numbers land in the registry as
``profile/{device_ms,host_ms,skew_ms}{phase=}`` gauges and
``profile/{sessions,steps}`` counters, so the capture is visible in the
telemetry stream it explains.

No session installed (the default) means ``record_step`` never touches
this module — trajectories stay bit-identical.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import time
from typing import Dict, List, Optional

from swiftmpi_tpu.obs.catalog import HOST_SPANS, UNSCOPED
from swiftmpi_tpu.obs.identity import process_rank

#: fleet-dir trigger file: ``{"id": n, "steps": k}``; ids increase so a
#: session replays each request exactly once.
TRIGGER_FILENAME = "profile_trigger.json"

#: per-capture summary schema (``profile_summary.json``); /2: reduced
#: from the ``.xplane.pb`` by self time through the compiled programs'
#: phase maps (was: chrome-trace events credited by substring).
PROFILE_SCHEMA = "smtpu-profile/2"

#: env pre-arm (set by ``launch.py -profile-at`` for every rank).
ENV_PROFILE_AT = "SMTPU_PROFILE_AT"
ENV_PROFILE_STEPS = "SMTPU_PROFILE_STEPS"

#: trace planes and lines (TPU/GPU runtime names)
_DEVICE_PLANE = re.compile(r"^/device:\w+:\d+$")
_HOST_PLANE = re.compile(r"^/host:")
_OP_LINE, _MODULE_LINE = "XLA Ops", "XLA Modules"
#: ``%fusion.24 = f32[...] fusion(...`` (the device names an op event by
#: its HLO text) or a bare instruction name
_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")
_MODULE_RUN = re.compile(r"^(.*?)(\(\d+\))?$")


def request_profile(fleet_dir: str, steps: int = 5) -> dict:
    """Drop a capture request in ``fleet_dir`` for every rank's session
    to pick up.  Monotonic id = previous id + 1 (a stale file from a
    finished run is superseded, not replayed)."""
    path = os.path.join(fleet_dir, TRIGGER_FILENAME)
    prev = 0
    try:
        with open(path) as f:
            prev = int(json.load(f).get("id", 0))
    except (OSError, ValueError):
        pass
    req = {"id": prev + 1, "steps": int(steps), "ts": time.time()}
    os.makedirs(fleet_dir, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(req, f)
    os.replace(tmp, path)
    return req


# -- trace reduction --------------------------------------------------------

def self_times(events) -> list:
    """``[name, self_ns, start_ns]`` per event of ONE trace line: an
    event's duration minus what its direct children cover, so the
    values sum to the union of the events (nested time counted once).
    ``events``: ``(start_ns, end_ns, name)``, properly nested or
    disjoint, as a device's op line is."""
    out: List[list] = []
    stack: List[tuple] = []          # (index into out, end_ns)
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            e = min(e, stack[-1][1])          # a child ends with its parent
            out[stack[-1][0]][1] -= e - s
        if e > s:
            out.append([name, e - s, s])
            stack.append((len(out) - 1, e))
    return out


def _line_events(line) -> list:
    return [(float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns),
             str(ev.name)) for ev in line.events]


def reduce_profile(data, maps: Optional[Dict[str, dict]] = None) -> dict:
    """One capture (a ``jax.profiler.ProfileData``, or anything shaped
    like it: ``planes[].lines[].events[]`` with ``name`` / ``start_ns``
    / ``duration_ns``) -> ``{device_ms, host_ms, modules_ms, module_runs,
    busy_ms, devices, events, unmatched}``.

    Device side: self time of every op event, booked under the phase
    the compiled program's map (``maps``: hlo module name ->
    ``obs.costs.phase_map`` result) gives its instruction; an op of a
    program with no map, or under no scope, is ``"unscoped"``.  Which
    program an op belongs to comes from the device's module line, so
    ``fusion.16`` of two programs stay apart.  Values are milliseconds,
    the mean over the capture's device planes; ``module_runs`` counts each
    program's executions the same way (dispatch runs ahead of the device,
    so a window of N consumed steps need not hold N executions: divide
    by this, not by ``steps``, for time an execution).  Host side: summed
    duration of the ``obs.span`` events (``catalog.HOST_SPANS``) over all
    host threads."""
    maps = maps or {}
    device_ns: Dict[str, float] = {}
    modules_ns: Dict[str, float] = {}
    module_runs: Dict[str, float] = {}
    host_ns: Dict[str, float] = {}
    n_dev = n_events = unmatched = 0

    def add(acc, key, value):
        acc[key] = acc.get(key, 0.0) + value

    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).append(_line_events(line))
        if _HOST_PLANE.match(plane.name):
            for s, e, name in (ev for line in lines.values()
                               for evs in line for ev in evs):
                if name in HOST_SPANS:
                    n_events += 1
                    add(host_ns, name, e - s)
            continue
        if not _DEVICE_PLANE.match(plane.name) or _OP_LINE not in lines:
            continue
        n_dev += 1
        runs = sorted(ev for evs in lines.get(_MODULE_LINE, ())
                      for ev in evs)
        starts = [r[0] for r in runs]
        programs = [_MODULE_RUN.match(r[2]).group(1) for r in runs]
        for program in programs:
            add(module_runs, program, 1)
        for ops in lines[_OP_LINE]:
            n_events += len(ops)
            for text, ns, at in self_times(ops):
                i = bisect.bisect_right(starts, at) - 1
                program = programs[i] if i >= 0 and at < runs[i][1] \
                    else "(no program)"
                phase = UNSCOPED
                pm = maps.get(program)
                if pm is not None:
                    name = _INSTRUCTION.match(text)
                    phase = pm["phase"].get(name.group(1) if name else text)
                    if phase is None:
                        unmatched += 1
                        phase = UNSCOPED
                add(device_ns, phase, ns)
                add(modules_ns, program, ns)
    per_dev = 1e6 * max(n_dev, 1)
    device_ms = {k: v / per_dev for k, v in device_ns.items()}
    return {"device_ms": device_ms,
            "host_ms": {k: v / 1e6 for k, v in host_ns.items()},
            "modules_ms": {k: v / per_dev for k, v in modules_ns.items()},
            "module_runs": {k: v / max(n_dev, 1)
                            for k, v in module_runs.items()},
            "busy_ms": sum(device_ms.values()), "devices": n_dev,
            "events": n_events, "unmatched": unmatched}


def _xplane_files(root: str) -> List[str]:
    return sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                            recursive=True))


def parse_trace_dir(root: str, maps: Optional[Dict[str, dict]] = None,
                    skip=()) -> dict:
    """Reduce every ``.xplane.pb`` under ``root`` (:func:`reduce_profile`;
    but those in ``skip``: an earlier capture's, left in the same
    directory) into the ``smtpu-profile/2`` summary.  ``maps`` defaults to
    the phase maps of every program tracked in this process — computing
    them lowers and compiles (a compile-cache read), which is why this
    runs after the capture has stopped, never inside it.  ``device_ms`` by
    phase (``"unscoped"`` included) sums to ``busy_ms``, the capture's
    device busy time; ``skew_ms`` is host minus device time under one
    name."""
    from jax.profiler import ProfileData

    if maps is None:
        from swiftmpi_tpu.obs import costs
        maps = costs.phase_maps()
    files = [p for p in _xplane_files(root) if p not in skip]
    out = {"device_ms": {}, "host_ms": {}, "modules_ms": {},
           "module_runs": {}, "busy_ms": 0.0, "devices": 0, "events": 0,
           "unmatched": 0}
    for path in files:
        try:
            part = reduce_profile(ProfileData.from_file(path), maps)
        except Exception:     # noqa: BLE001 — a torn capture file
            continue
        for k, v in part.items():
            if isinstance(v, dict):
                for name, ms in v.items():
                    out[k][name] = out[k].get(name, 0.0) + ms
            else:
                out[k] += v
    device_ms, host_ms = out["device_ms"], out["host_ms"]
    out["skew_ms"] = {ph: host_ms.get(ph, 0.0) - device_ms.get(ph, 0.0)
                      for ph in set(device_ms) | set(host_ms)}
    out.update(schema=PROFILE_SCHEMA, files=len(files),
               programs=sorted(maps))
    return out


# -- the session ------------------------------------------------------------

class ProfileSession:
    """One rank's triggered-capture state machine.  Single-threaded by
    construction: every transition happens on the trainer thread inside
    ``obs.record_step`` (anomaly requests only park a flag)."""

    def __init__(self, profile_dir: str = os.path.join("runs",
                                                       "profiles"),
                 steps: int = 5, profile_at: int = -1,
                 fleet_dir: Optional[str] = None,
                 poll_s: float = 1.0,
                 capture_on_anomaly: bool = False):
        self.profile_dir = profile_dir
        self.steps = max(int(steps), 1)
        self.profile_at = int(profile_at)
        self.fleet_dir = fleet_dir or None
        self.poll_s = poll_s
        self.capture_on_anomaly = capture_on_anomaly
        self.captures: List[dict] = []
        self._consumed = 0
        self._active: Optional[dict] = None
        self._pending: Optional[dict] = None
        self._done_trigger_id = 0
        self._last_poll = 0.0

    # -- triggers ----------------------------------------------------------
    def request(self, steps: Optional[int] = None,
                reason: str = "manual") -> None:
        """Ask for a capture at the next consumed step.  Safe from any
        thread (it only parks a dict); ignored while one is already
        pending or active."""
        if self._active is None and self._pending is None:
            self._pending = {"steps": int(steps or self.steps),
                             "reason": reason}

    def _poll_trigger(self) -> None:
        now = time.monotonic()
        if now - self._last_poll < self.poll_s:
            return
        self._last_poll = now
        try:
            with open(os.path.join(self.fleet_dir,
                                   TRIGGER_FILENAME)) as f:
                req = json.load(f)
        except (OSError, ValueError):
            return
        tid = int(req.get("id", 0))
        if tid <= self._done_trigger_id:
            return
        self._done_trigger_id = tid
        self.request(steps=int(req.get("steps", self.steps)),
                     reason=f"trigger:{tid}")

    # -- the step funnel ---------------------------------------------------
    def on_step(self, n: int = 1) -> None:
        """Account ``n`` consumed steps; start/stop captures at step
        granularity (a fused group of L steps counts as L — a capture
        window never splits a dispatch)."""
        self._consumed += n
        if self._active is not None:
            self._active["remaining"] -= n
            if self._active["remaining"] <= 0:
                self._stop()
            return
        if 0 <= self.profile_at <= self._consumed:
            self.profile_at = -1      # the knob fires once
            self._start(self.steps, "profile_at")
            return
        if self._pending is None and self.fleet_dir:
            self._poll_trigger()
        if self._pending is not None:
            p, self._pending = self._pending, None
            self._start(p["steps"], p["reason"])

    def close(self) -> None:
        """Finish an in-flight capture (end of training mid-window)."""
        if self._active is not None:
            self._stop()

    # -- capture lifecycle -------------------------------------------------
    @staticmethod
    def _drain() -> None:
        """Wait until every program already dispatched has run.  Dispatch
        runs ahead of the device (by seconds, where a step takes 200 ms),
        so without this a window of N consumed steps holds whatever the
        device happened to run meanwhile, cut at both ends; drained at
        start and stop it holds exactly those N steps' executions.  A
        device runs its programs in order: one more, tiny, is done when
        all before it are."""
        import jax
        jax.block_until_ready([jax.device_put(0, d) + 1
                               for d in jax.local_devices()])

    def _start(self, steps: int, reason: str) -> None:
        import jax
        out = os.path.join(
            self.profile_dir,
            f"profile_step{self._consumed}_r{process_rank() or 0}")
        try:
            os.makedirs(out, exist_ok=True)
            before = set(_xplane_files(out))
            self._drain()
            jax.profiler.start_trace(out, create_perfetto_trace=True)
        except Exception:
            return   # a second profiler on the host must not kill train
        self._active = {"dir": out, "start_step": self._consumed,
                        "steps": steps, "remaining": steps,
                        "reason": reason, "t0": time.perf_counter(),
                        "before": before}
        from swiftmpi_tpu import obs
        obs.get_registry().counter("profile/sessions").inc()

    def _stop(self) -> None:
        import jax
        act, self._active = self._active, None
        try:
            self._drain()
            jax.profiler.stop_trace()
        except Exception:
            pass
        captured = act["steps"] - max(act["remaining"], 0)
        summary = parse_trace_dir(act["dir"], skip=act["before"])
        summary.update(
            run_dir=act["dir"], reason=act["reason"],
            start_step=act["start_step"],
            steps=captured, rank=process_rank() or 0,
            wall_ms=(time.perf_counter() - act["t0"]) * 1e3)
        try:
            with open(os.path.join(act["dir"],
                                   "profile_summary.json"), "w") as f:
                json.dump(summary, f, indent=1, sort_keys=True)
        except OSError:
            pass
        from swiftmpi_tpu import obs
        reg = obs.get_registry()
        reg.counter("profile/steps").inc(captured)
        for ph, v in summary["device_ms"].items():
            reg.gauge("profile/device_ms", phase=ph).set(v)
        for ph, v in summary["host_ms"].items():
            reg.gauge("profile/host_ms", phase=ph).set(v)
        for ph, v in summary["skew_ms"].items():
            reg.gauge("profile/skew_ms", phase=ph).set(v)
        rec = obs.get_recorder()
        if rec is not None:
            rec.event("profile/capture",
                      {k: summary[k] for k in
                       ("run_dir", "reason", "start_step", "steps",
                        "files", "events")})
        self.captures.append(summary)


def on_critical_anomaly(anomaly: dict) -> None:
    """Numerics-plane hook: a critical anomaly captures the very steps
    that misbehaved.  No-op unless a session with
    ``capture_on_anomaly`` is installed."""
    from swiftmpi_tpu import obs
    sess = obs.get_profiler()
    if sess is not None and sess.capture_on_anomaly:
        sess.request(reason=f"anomaly:{anomaly.get('anomaly', '?')}")


def main(argv: Optional[list] = None) -> int:
    """``python -m swiftmpi_tpu.obs.profiler <fleet_dir> [--steps N]``:
    request an N-step capture from every rank of a live fleet run."""
    import argparse
    ap = argparse.ArgumentParser(
        description="drop a profile trigger in a fleet dir")
    ap.add_argument("fleet_dir", help="launch.py -fleet-dir target")
    ap.add_argument("--steps", type=int, default=5,
                    help="capture window length in consumed steps")
    args = ap.parse_args(argv)
    req = request_profile(args.fleet_dir, steps=args.steps)
    print(f"profile trigger id={req['id']} steps={req['steps']} "
          f"written to {os.path.join(args.fleet_dir, TRIGGER_FILENAME)}")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
