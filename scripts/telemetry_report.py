#!/usr/bin/env python
"""Run analyzer over a telemetry JSONL file (obs.StepRecorder output).

Turns the raw ``smtpu-telemetry/1`` stream into the three questions an
operator actually asks after a run:

* **Where did the time go?**  Per-phase latency breakdown — p50/p95/p99
  milliseconds for every ``phase_ms{phase=...}`` histogram (render, h2d,
  input_wait, dispatch, window_dedup, checkpoint_save, ...), recomputed
  from the bucket counts so the report works on a crashed run with no
  summary line.
* **What did the wire format decide?**  The window-coalesced push picks
  sparse vs dense per window by measured density
  (transfer/window.py); the per-step ``transfer/window_*`` counter
  deltas reconstruct that decision sequence as a compressed timeline
  (``steps 0-39: sparse  steps 40-47: dense ...``) — the artifact to
  read when wire bytes regress.
* **How much traffic?**  Cumulative ``transfer/*`` counters per backend
  with per-step averages, plus the host-stall split from the training
  samplers.
* **What did the autotuner do?**  The control plane's out-of-band
  ``control/decision`` events become a decision timeline — knob value
  over steps with the triggering evidence (win, streak, traffic delta)
  — so every knob change in a run is traceable to what it saw.

Usage::

    python scripts/telemetry_report.py telemetry.jsonl
    python scripts/telemetry_report.py telemetry.jsonl --json  # machine
    python scripts/telemetry_report.py telemetry.jsonl --phases-only

Exit codes: 0 ok, 2 unreadable/empty/not-telemetry input.  No repo
imports on purpose — the file is copied off the worker host and
analyzed where the package is not installed.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

SCHEMA_PREFIX = "smtpu-telemetry/"


# -- series names ---------------------------------------------------------
def parse_series_key(key: str) -> Tuple[str, Dict[str, str]]:
    """``name{k=v,k2=v2}`` -> (name, labels).  Mirrors
    obs/registry.series_key (sorted label order is the writer's job)."""
    if "{" not in key:
        return key, {}
    name, _, rest = key.partition("{")
    labels = {}
    for part in rest.rstrip("}").split(","):
        if part:
            k, _, v = part.partition("=")
            labels[k] = v
    return name, labels


def _quantile(counts: List[int], bounds: List[float], q: float) -> float:
    """Interpolated quantile from cumulative-free bucket counts; same
    rule as obs/registry.quantile_from_buckets (overflow bucket clamps
    to the top finite edge)."""
    total = sum(counts)
    if total == 0:
        return 0.0
    rank = q * total
    seen = 0.0
    for i, c in enumerate(counts):
        if c == 0:
            continue
        if seen + c >= rank:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i] if i < len(bounds) else bounds[-1]
            frac = (rank - seen) / c
            return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
        seen += c
    return bounds[-1] if bounds else 0.0


# -- load -----------------------------------------------------------------
def repair_json_line(line: str) -> Optional[dict]:
    """Best-effort parse of a truncated JSON object line — the tail a
    crashed rank left mid-``write``.  Balances an unterminated string
    and unclosed brackets, retrying progressively shorter prefixes; a
    twin of obs/collector.repair_json_line (this script must stay free
    of repo imports) — keep the two in sync."""
    s = line.strip()
    if not s.startswith("{"):
        return None
    for cut in range(len(s), max(len(s) - 4096, 0), -1):
        prefix = s[:cut]
        stack: List[str] = []
        in_str = esc = False
        for ch in prefix:
            if esc:
                esc = False
            elif ch == "\\":
                esc = True
            elif ch == '"':
                in_str = not in_str
            elif not in_str and ch in "{[":
                stack.append(ch)
            elif not in_str and ch in "}]":
                if not stack:
                    break
                stack.pop()
        else:
            if esc:
                continue
            closed = prefix + ('"' if in_str else "")
            for b in reversed(stack):
                closed += "}" if b == "{" else "]"
            try:
                obj = json.loads(closed)
            except ValueError:
                continue
            if isinstance(obj, dict):
                return obj
    return None


def load(path: str) -> dict:
    """Parse the JSONL into {"meta", "steps": [...], "events": [...],
    "heartbeats": n, "summary"|None, "recovery": {...}} — "events"
    collects the out-of-band ``control/*`` and ``numerics/*`` lines.

    Crashed-run tolerance: a truncated FINAL line is repair-parsed
    (``recovery.recovered``); other undecodable lines are counted as
    ``recovery.dropped`` instead of aborting, and a stream whose meta
    line itself was lost still loads (meta synthesized) as long as the
    surviving records look like telemetry.  SystemExit(2) only on
    unreadable / empty / provably-not-telemetry input."""
    try:
        with open(path) as f:
            lines = [ln for ln in f if ln.strip()]
    except OSError as e:
        print(f"telemetry_report: cannot read {path}: {e}",
              file=sys.stderr)
        raise SystemExit(2)
    if not lines:
        print(f"telemetry_report: {path} is empty", file=sys.stderr)
        raise SystemExit(2)
    records: List[dict] = []
    recovered = dropped = 0
    last = len(lines) - 1
    for n, ln in enumerate(lines):
        try:
            rec = json.loads(ln)
        except ValueError:
            if n == last:
                rec = repair_json_line(ln)
                if rec is not None:
                    rec["repaired"] = True
                    records.append(rec)
                    recovered += 1
                    continue
            dropped += 1
            print(f"telemetry_report: {path}: dropped bad JSON on "
                  f"line {n + 1}", file=sys.stderr)
            continue
        if isinstance(rec, dict):
            records.append(rec)
        else:
            dropped += 1
    meta = next((r for r in records if r.get("kind") == "meta"), None)
    if meta is not None and \
            not str(meta.get("schema", "")).startswith(SCHEMA_PREFIX):
        print(f"telemetry_report: {path} is not a telemetry stream "
              f"(schema={meta.get('schema')!r})", file=sys.stderr)
        raise SystemExit(2)
    if meta is None:
        # truncation ate the first line: accept the stream iff the
        # surviving records carry the telemetry shape ("v" + step/...)
        if not any(r.get("kind") in ("step", "summary", "heartbeat")
                   and "v" in r for r in records):
            print(f"telemetry_report: {path} is not a telemetry stream "
                  f"(no meta line, no telemetry records)",
                  file=sys.stderr)
            raise SystemExit(2)
        meta = {"schema": SCHEMA_PREFIX + "?", "run": "?",
                "synthesized": True}
    steps, events, summary = [], [], None
    heartbeats = 0
    for rec in records:
        kind = rec.get("kind")
        if kind == "step":
            steps.append(rec)
        elif kind == "summary":
            summary = rec
        elif kind == "heartbeat":
            heartbeats += 1
        elif isinstance(kind, str) and (kind.startswith("control/")
                                        or kind.startswith("numerics/")
                                        or kind.startswith("profile/")
                                        or kind.startswith("trace/")):
            events.append(rec)
    return {"meta": meta, "steps": steps, "events": events,
            "heartbeats": heartbeats, "summary": summary,
            "recovery": {"recovered": recovered, "dropped": dropped}}


# -- analyses -------------------------------------------------------------
def phase_table(doc: dict) -> List[dict]:
    """Aggregate every histogram across step records (bounds are emitted
    once per key, on first appearance) and compute quantiles.  Covers
    phase_ms plus any other histogram (health/probe_ms, bench step_ms)."""
    acc: Dict[str, dict] = {}
    for rec in doc["steps"]:
        for key, h in (rec.get("hists") or {}).items():
            a = acc.setdefault(key, {"counts": None, "bounds": None,
                                     "n": 0, "sum": 0.0})
            if h.get("bounds") is not None:
                a["bounds"] = list(h["bounds"])
            counts = h.get("counts") or []
            if a["counts"] is None:
                a["counts"] = list(counts)
            else:
                for i, c in enumerate(counts):
                    a["counts"][i] += c
            a["n"] += int(h.get("n", 0))
            a["sum"] += float(h.get("sum", 0.0))
    rows = []
    for key in sorted(acc):
        a = acc[key]
        if not a["n"] or a["bounds"] is None:
            continue
        name, labels = parse_series_key(key)
        rows.append({
            "series": key,
            "phase": labels.get("phase", name),
            "n": a["n"],
            "mean_ms": a["sum"] / a["n"],
            "p50_ms": _quantile(a["counts"], a["bounds"], 0.50),
            "p95_ms": _quantile(a["counts"], a["bounds"], 0.95),
            "p99_ms": _quantile(a["counts"], a["bounds"], 0.99),
            "total_ms": a["sum"],
        })
    rows.sort(key=lambda r: -r["total_ms"])
    return rows


def wire_timeline(doc: dict) -> List[dict]:
    """Per-step wire-format decision runs, compressed.  A step's
    decision is whichever ``transfer/window_fmt{fmt=...}`` label moved
    in its record (dense/sparse/q/bitmap — the 4-way crossover); runs
    recorded before the fmt counter existed fall back to the legacy
    2-way ``transfer/window_{sparse,dense}`` counters.  Multiple
    formats moving in one record (several windows closed) label the
    step ``mixed``."""
    runs: List[dict] = []
    for rec in doc["steps"]:
        decisions = set()
        legacy = set()
        for key, delta in (rec.get("counters") or {}).items():
            name, labels = parse_series_key(key)
            if delta <= 0:
                continue
            if name == "transfer/window_fmt":
                decisions.add(labels.get("fmt", "?"))
            elif name.startswith("transfer/window_"):
                legacy.add(name[len("transfer/window_"):])
        # the fmt series is strictly finer (sparse_q/bitmap also bump
        # the legacy sparse counter) — prefer it whenever present
        if not decisions:
            decisions = legacy
        if not decisions:
            continue
        label = decisions.pop() if len(decisions) == 1 else "mixed"
        step = int(rec["step"])
        if runs and runs[-1]["decision"] == label \
                and runs[-1]["last"] == step - int(rec.get("steps", 1)):
            runs[-1]["last"] = step
            runs[-1]["windows"] += 1
        else:
            runs.append({"decision": label, "first": step, "last": step,
                         "windows": 1})
    return runs


def decision_timeline(doc: dict) -> List[dict]:
    """The control plane's knob trajectory: one row per
    ``control/decision`` event, ordered by step, carrying the knob's
    value transition and the evidence that triggered it.  Evaluations
    that held every knob emit no decision, so the timeline is exactly
    the changes (and near-changes: deferred streak ticks ride along,
    marked by their action)."""
    rows = []
    for rec in doc["events"]:
        if rec.get("kind") != "control/decision":
            continue
        rows.append({
            "step": int(rec.get("step", 0)),
            "knob": rec.get("knob", "?"),
            "action": rec.get("action", "?"),
            "old": rec.get("old"),
            "new": rec.get("new"),
            "win": rec.get("win"),
            "streak": rec.get("streak"),
            "evidence": rec.get("evidence") or {},
            "traffic_delta": rec.get("traffic_delta") or {},
        })
    rows.sort(key=lambda r: r["step"])
    return rows


def control_summary(doc: dict) -> dict:
    """Evaluation/decision counts for gates: decisions per 1k steps is
    the traffic-budget metric that catches a flapping tuner."""
    evals = sum(1 for r in doc["events"]
                if r.get("kind") == "control/evaluation")
    decisions = [r for r in doc["events"]
                 if r.get("kind") == "control/decision"]
    applied = sum(1 for r in decisions if r.get("action") == "apply")
    steps = (int(doc["summary"].get("steps", 0))
             if doc["summary"] is not None else
             sum(int(r.get("steps", 1)) for r in doc["steps"]))
    out = {"evaluations": evals, "decisions": len(decisions),
           "applied": applied, "steps": steps}
    if steps:
        out["decisions_per_1k_steps"] = 1000.0 * len(decisions) / steps
    return out


def numerics_summary(doc: dict) -> dict:
    """The training-numerics health plane (obs/numerics.py): per-series
    min/mean/max/last over the ``numerics/*`` gauges sampled into step
    records, cumulative nonfinite/quant-error counters, and the
    out-of-band ``numerics/anomaly`` event timeline with severity
    counts.  Empty when ``[obs] numerics`` was off for the run."""
    series: Dict[str, dict] = {}
    for rec in doc["steps"]:
        step = int(rec.get("step", 0))
        for key, v in (rec.get("gauges") or {}).items():
            if not key.startswith("numerics/"):
                continue
            try:
                v = float(v)
            except (TypeError, ValueError):
                continue
            s = series.setdefault(key, {"n": 0, "sum": 0.0,
                                        "min": v, "max": v,
                                        "last": v, "last_step": step})
            s["n"] += 1
            s["sum"] += v
            s["min"] = min(s["min"], v)
            s["max"] = max(s["max"], v)
            s["last"], s["last_step"] = v, step
    rows = []
    for key in sorted(series):
        s = series[key]
        rows.append({"series": key, "n": s["n"],
                     "mean": s["sum"] / s["n"], "min": s["min"],
                     "max": s["max"], "last": s["last"],
                     "last_step": s["last_step"]})
    counters: Dict[str, float] = {}
    if doc["summary"] is not None:
        totals = doc["summary"].get("counters") or {}
    else:
        totals = {}
        for rec in doc["steps"]:
            for key, delta in (rec.get("counters") or {}).items():
                totals[key] = totals.get(key, 0.0) + delta
    for key, v in totals.items():
        name, _ = parse_series_key(key)
        if name.startswith("numerics/"):
            counters[key] = counters.get(key, 0.0) + float(v)
    anomalies = []
    severities: Dict[str, int] = {}
    for rec in doc["events"]:
        if rec.get("kind") != "numerics/anomaly":
            continue
        sev = str(rec.get("severity", "?"))
        severities[sev] = severities.get(sev, 0) + 1
        anomalies.append({
            "step": int(rec.get("step", 0)),
            "anomaly": rec.get("anomaly", "?"),
            "severity": sev,
            "series": rec.get("series"),
            "value": rec.get("value"),
            "baseline": rec.get("baseline"),
            "z": rec.get("z"),
        })
    anomalies.sort(key=lambda a: a["step"])
    return {"series": rows, "counters": counters,
            "anomalies": anomalies, "severities": severities,
            "nonfinite_total": sum(
                v for k, v in counters.items()
                if parse_series_key(k)[0] == "numerics/nonfinite")}


def compile_summary(doc: dict, catalog: Optional[dict] = None) -> dict:
    """The compiler-cost plane (obs/costs.py): per-fn compile, retrace
    and compile-ms totals from the ``compile/*{fn=}`` counters, the
    last XLA-measured flops/bytes/peak gauges, and — when a
    ``smtpu-costs/1`` catalog doc is supplied — the catalog's
    hand-model drift columns merged in.  ``profile/capture`` events
    (triggered profiler windows) ride along as a timeline.  Empty when
    ``[obs] costs`` was off for the run."""
    if doc["summary"] is not None:
        totals = dict(doc["summary"].get("counters") or {})
    else:
        totals = {}
        for rec in doc["steps"]:
            for key, delta in (rec.get("counters") or {}).items():
                totals[key] = totals.get(key, 0.0) + delta
    fns: Dict[str, dict] = {}

    def fn_row(labels):
        return fns.setdefault(labels.get("fn", "?"), {
            "compiles": 0, "retraces": 0, "compile_ms": 0.0})

    for key, v in totals.items():
        name, labels = parse_series_key(key)
        if name == "compile/compiles":
            fn_row(labels)["compiles"] += int(v)
        elif name == "compile/retraces":
            fn_row(labels)["retraces"] += int(v)
        elif name == "compile/compile_ms":
            fn_row(labels)["compile_ms"] += float(v)
    for rec in doc["steps"]:
        for key, v in (rec.get("gauges") or {}).items():
            name, labels = parse_series_key(key)
            if name == "compile/flops":
                fn_row(labels)["flops"] = float(v)
            elif name == "compile/bytes":
                fn_row(labels)["bytes"] = float(v)
            elif name == "compile/peak_bytes":
                fn_row(labels)["peak_bytes"] = float(v)
    cat_fns = (catalog or {}).get("fns") or {}
    for name, e in cat_fns.items():
        row = fns.setdefault(name, {"compiles": int(e.get("compiles", 0)),
                                    "retraces": int(e.get("retraces", 0)),
                                    "compile_ms": float(
                                        e.get("compile_ms_total", 0.0))})
        for k in ("flops", "bytes_accessed", "peak_bytes",
                  "steps_per_call", "hand_flops", "hand_bytes",
                  "flops_drift_pct", "bytes_drift_pct"):
            if e.get(k) is not None:
                row["bytes" if k == "bytes_accessed" else k] = e[k]
    captures = []
    for rec in doc["events"]:
        if rec.get("kind") != "profile/capture":
            continue
        captures.append({k: rec.get(k) for k in
                         ("step", "run_dir", "reason", "start_step",
                          "steps", "files", "events")})
    captures.sort(key=lambda c: c.get("step") or 0)
    return {"fns": fns, "captures": captures,
            "retraces_total": sum(r["retraces"] for r in fns.values()),
            "compile_ms_total": sum(r["compile_ms"]
                                    for r in fns.values())}


def traffic_summary(doc: dict) -> dict:
    """Cumulative counters (prefer the summary line's authoritative
    totals; fall back to summing step deltas for a crashed run) grouped
    as transfer-per-backend / train / everything-else."""
    gauges = {}
    if doc["summary"] is not None:
        totals = dict(doc["summary"].get("counters") or {})
        steps = int(doc["summary"].get("steps", 0))
        gauges = doc["summary"].get("gauges") or {}
    else:
        totals = {}
        steps = 0
        for rec in doc["steps"]:
            steps += int(rec.get("steps", 1))
            for key, delta in (rec.get("counters") or {}).items():
                totals[key] = totals.get(key, 0.0) + delta
            gauges = rec.get("gauges") or gauges
    transfer: Dict[str, dict] = {}
    # the train/ gauges' last values ride with the train/ totals, label
    # kept: sampler_slot_lookups{mode=per_draw}
    train = {key[len("train/"):]: v for key, v in gauges.items()
             if key.startswith("train/")}
    other = {}
    for key, total in sorted(totals.items()):
        name, labels = parse_series_key(key)
        if name.startswith("transfer/"):
            backend = labels.get("backend", "?")
            if name == "transfer/window_fmt":
                # labeled decision counter: fold the fmt label into the
                # metric name so the four series don't collide on one
                # dict key (and so gate scripts see window_fmt_<fmt>)
                k = "window_fmt_" + labels.get("fmt", "?")
                bd = transfer.setdefault(backend, {})
                bd[k] = bd.get(k, 0.0) + total
            elif name == "transfer/collective":
                # same folding for the hot-plane collective decision
                # mix: kind= label -> collective_psum /
                # collective_sparse_ar (the ledger key names, so the
                # budget gate's collective-mix floor sees live JSONL)
                k = "collective_" + labels.get("kind", "?")
                bd = transfer.setdefault(backend, {})
                bd[k] = bd.get(k, 0.0) + total
            elif name == "transfer/pull_fmt":
                # pull-family decision mix: fmt= label ->
                # pull_fmt_full / pull_fmt_bf16 / pull_fmt_q (the
                # ledger key names the budget gate's pull guard reads)
                k = "pull_fmt_" + labels.get("fmt", "?")
                bd = transfer.setdefault(backend, {})
                bd[k] = bd.get(k, 0.0) + total
            else:
                transfer.setdefault(backend, {})[
                    name[len("transfer/"):]] = total
        elif name.startswith("train/"):
            train[name[len("train/"):]] = total
        else:
            other[key] = total
    out = {"steps": steps, "transfer": transfer, "train": train,
           "other": other}
    if steps:
        out["per_step"] = {
            b: {k: v / steps for k, v in m.items()}
            for b, m in transfer.items()}
        stall = train.get("host_stall_ms_total")
        if stall is not None:
            out["stall_ms_per_step"] = stall / steps
    return out


def pull_summary(doc: dict) -> dict:
    """Delta-pull plane section (ISSUE 20): per-backend hit ratio and
    pull decision mix from the cumulative ledger, plus a bytes-saved
    timeline bucketed over the run (per-step
    ``transfer/pull_bytes_saved`` / ``pull_cache_hits`` deltas summed
    across backends).  Hit ratio denominates on the cacheable rows —
    ``pull_rows - pull_hot_rows`` — because hybrid hot-replica reads
    are already 0 bytes and never enter the cache."""
    traffic = traffic_summary(doc)
    backends = {}
    for b, m in (traffic.get("transfer") or {}).items():
        if not any(k.startswith("pull") for k in m):
            continue
        rows = m.get("pull_rows", 0.0)
        hot = m.get("pull_hot_rows", 0.0)
        hits = m.get("pull_cache_hits", 0.0)
        cacheable = max(rows - hot, 0.0)
        backends[b] = {
            "pull_rows": rows, "pull_hot_rows": hot,
            "pull_cache_hits": hits,
            "pull_delta_rows": m.get("pull_delta_rows", 0.0),
            "pull_bytes": m.get("pull_bytes", 0.0),
            "pull_bytes_saved": m.get("pull_bytes_saved", 0.0),
            "hit_ratio": hits / cacheable if cacheable else 0.0,
            "fmt": {k[len("pull_fmt_"):]: v for k, v in m.items()
                    if k.startswith("pull_fmt_")},
        }
    deltas = []
    for rec in doc["steps"]:
        saved = hits = 0.0
        moved = False
        for key, delta in (rec.get("counters") or {}).items():
            name, _ = parse_series_key(key)
            if name == "transfer/pull_bytes_saved":
                saved += delta
                moved = True
            elif name == "transfer/pull_cache_hits":
                hits += delta
                moved = True
        if moved and "step" in rec:
            deltas.append((int(rec["step"]), saved, hits))
    timeline = []
    if deltas:
        per = max(1, (len(deltas) + 11) // 12)    # <= 12 buckets
        for i in range(0, len(deltas), per):
            chunk = deltas[i:i + per]
            timeline.append({
                "first": chunk[0][0], "last": chunk[-1][0],
                "bytes_saved": sum(c[1] for c in chunk),
                "hits": sum(c[2] for c in chunk)})
    return {"backends": backends, "timeline": timeline,
            "steps": traffic.get("steps", 0)}


def _print_pull(pull: dict) -> None:
    print()
    print(f"delta-pull plane over {pull['steps']} step(s):")
    if not pull["backends"]:
        print("  (no pull counters — traffic counting off or no pulls)")
        return
    for b, m in sorted(pull["backends"].items()):
        fmt = ", ".join(f"{k}={v:g}" for k, v in sorted(m["fmt"].items())
                        if v)
        print(f"  backend={b}: hit_ratio={m['hit_ratio']:.3f} "
              f"({m['pull_cache_hits']:,.0f} hits / "
              f"{m['pull_rows']:,.0f} rows, "
              f"{m['pull_hot_rows']:,.0f} hot@0B)")
        print(f"    pull_bytes={m['pull_bytes']:,.0f} "
              f"saved={m['pull_bytes_saved']:,.0f} "
              f"delta_rows={m['pull_delta_rows']:,.0f}"
              + (f"  decisions: {fmt}" if fmt else ""))
    if pull["timeline"]:
        print("  bytes-saved timeline:")
        for t in pull["timeline"]:
            span = (f"step {t['first']}" if t["first"] == t["last"]
                    else f"steps {t['first']}-{t['last']}")
            print(f"    {span}: {t['bytes_saved']:,.0f} B saved, "
                  f"{t['hits']:,.0f} hit(s)")


def report(doc: dict, phases_only: bool = False,
           catalog: Optional[dict] = None) -> dict:
    out = {"meta": {k: doc["meta"].get(k)
                    for k in ("schema", "run", "rank", "ident", "pid")},
           "phases": phase_table(doc)}
    rec = doc.get("recovery") or {}
    if rec.get("recovered") or rec.get("dropped"):
        out["recovery"] = rec
    if not phases_only:
        out["wire_timeline"] = wire_timeline(doc)
        out["traffic"] = traffic_summary(doc)
        out["decisions"] = decision_timeline(doc)
        out["control"] = control_summary(doc)
        out["numerics"] = numerics_summary(doc)
        out["compile"] = compile_summary(doc, catalog=catalog)
    return out


# -- fleet mode (smtpu-fleet/1) -------------------------------------------
FLEET_SCHEMA_PREFIX = "smtpu-fleet/"


def load_fleet(path: str) -> dict:
    """Load a merged ``smtpu-fleet/1`` timeline (obs.FleetCollector
    output), or — given a fleet DIRECTORY — its ``fleet.jsonl`` when
    present, else a lean standalone merge of the per-rank streams (no
    repo imports, so this works off-host like the rest of the script).
    """
    import os
    if os.path.isdir(path):
        merged = os.path.join(path, "fleet.jsonl")
        if os.path.isfile(merged):
            path = merged
        else:
            return _merge_fleet_dir(path)
    try:
        with open(path) as f:
            lines = [ln for ln in f if ln.strip()]
    except OSError as e:
        print(f"telemetry_report: cannot read {path}: {e}",
              file=sys.stderr)
        raise SystemExit(2)
    doc = {"meta": None, "members": [], "sup": [], "health": [],
           "rows": [], "numerics": [], "summary": None}
    for n, ln in enumerate(lines):
        try:
            rec = json.loads(ln)
        except ValueError:
            rec = repair_json_line(ln) if n == len(lines) - 1 else None
            if rec is None:
                continue
        kind = rec.get("kind")
        if kind == "meta":
            doc["meta"] = rec
        elif kind == "member":
            doc["members"].append(rec)
        elif isinstance(kind, str) and kind.startswith("sup/"):
            doc["sup"].append(rec)
        elif kind == "health":
            doc["health"].append(rec)
        elif kind == "fleet_step":
            doc["rows"].append(rec)
        elif isinstance(kind, str) and kind.startswith("numerics/"):
            doc["numerics"].append(rec)
        elif kind == "summary":
            doc["summary"] = rec
    meta = doc["meta"]
    if meta is None or \
            not str(meta.get("schema", "")).startswith(
                FLEET_SCHEMA_PREFIX):
        print(f"telemetry_report: {path} is not a fleet timeline "
              f"(schema="
              f"{meta.get('schema') if meta else None!r})",
              file=sys.stderr)
        raise SystemExit(2)
    return doc


def _merge_fleet_dir(fleet_dir: str) -> dict:
    """Per-rank merge from raw streams when no fleet.jsonl exists yet:
    member rows + step-aligned skew, WITHOUT the collector's health
    machine (no supervisor correlation off-host — run smtpu_top or the
    collector on the host for that)."""
    import glob
    import os
    paths = sorted(glob.glob(os.path.join(fleet_dir,
                                          "telemetry_*.jsonl")))
    if not paths:
        print(f"telemetry_report: {fleet_dir}: no telemetry_*.jsonl "
              f"streams", file=sys.stderr)
        raise SystemExit(2)
    members, per_rank = [], {}
    for p in paths:
        try:
            d = load(p)
        except SystemExit:
            continue
        m = d["meta"]
        rank = str(m.get("rank") if m.get("rank") is not None
                   else m.get("ident") or os.path.basename(p))
        t0 = float(m.get("ts", 0.0))
        steps = {int(r["step"]): t0 + float(r.get("t", 0.0))
                 for r in d["steps"]}
        prev = per_rank.setdefault(rank, {})
        prev.update(steps)
        anom: Dict[str, int] = {}
        for ev in d["events"]:
            if ev.get("kind") == "numerics/anomaly":
                sev = str(ev.get("severity", "?"))
                anom[sev] = anom.get(sev, 0) + 1
        members.append({
            "kind": "member", "rank": rank, "ident": m.get("ident"),
            "pids": [m.get("pid")], "restarts": 0,
            "records": len(d["steps"]), "heartbeats": d["heartbeats"],
            "last_step": max(steps, default=None),
            "health": "exited" if d["summary"] is not None else "?",
            "exits": [], "anomalies": anom,
            "recovered": d["recovery"]["recovered"],
            "dropped": d["recovery"]["dropped"]})
    rows = []
    common = None
    for table in per_rank.values():
        common = set(table) if common is None else common & set(table)
    for step in sorted(common or ()):
        t = {r: per_rank[r][step] for r in per_rank}
        rows.append({"kind": "fleet_step", "step": step, "t": t,
                     "step_ms": {}, "wire": {},
                     "slowest": max(t, key=t.get)})
    return {"meta": {"kind": "meta",
                     "schema": FLEET_SCHEMA_PREFIX + "dir",
                     "run": os.path.basename(
                         os.path.normpath(fleet_dir)),
                     "ranks": sorted(per_rank)},
            "members": members, "sup": [], "health": [],
            "rows": rows, "summary": None}


def fleet_report(doc: dict) -> dict:
    """Machine-shaped fleet report: member table, supervisor events,
    compressed slowest-rank (skew) timeline, and the collector summary
    when present."""
    runs: List[dict] = []
    for row in doc["rows"]:
        slowest = row.get("slowest")
        if slowest is None:
            continue
        step = int(row["step"])
        if runs and runs[-1]["slowest"] == slowest:
            runs[-1]["last"] = step
            runs[-1]["rows"] += 1
            runs[-1]["skew_ms_max"] = max(runs[-1]["skew_ms_max"],
                                          float(row.get("skew_ms", 0.0)))
        else:
            runs.append({"slowest": slowest, "first": step,
                         "last": step, "rows": 1,
                         "skew_ms_max": float(row.get("skew_ms", 0.0))})
    return {"meta": {k: doc["meta"].get(k)
                     for k in ("schema", "run", "ranks")},
            "members": doc["members"], "sup_events": doc["sup"],
            "health_transitions": doc["health"],
            "skew_timeline": runs,
            "numerics_events": doc.get("numerics") or [],
            "summary": doc["summary"]}


def _print_fleet_report(rep: dict) -> None:
    m = rep["meta"]
    print(f"fleet run={m.get('run')} schema={m.get('schema')} "
          f"ranks={m.get('ranks')}")
    print()
    print("members:")
    for mb in rep["members"]:
        extra = ""
        if mb.get("restarts"):
            extra += f" restarts={mb['restarts']}"
        if mb.get("recovered") or mb.get("dropped"):
            extra += (f" recovered={mb.get('recovered', 0)}"
                      f" dropped={mb.get('dropped', 0)}")
        exits = mb.get("exits") or []
        if exits:
            e = exits[-1]
            extra += (f" exit(rc={e.get('rc')}, by_supervisor="
                      f"{e.get('by_supervisor')})")
        anom = mb.get("anomalies") or {}
        if anom:
            extra += " anomalies=" + ",".join(
                f"{k}:{anom[k]}" for k in sorted(anom))
        print(f"  rank {mb['rank']}: {mb.get('health', '?'):8s}"
              f" last_step={mb.get('last_step')}"
              f" records={mb.get('records')}"
              f" heartbeats={mb.get('heartbeats')}{extra}")
    if rep["sup_events"]:
        print()
        print("supervisor events:")
        for ev in rep["sup_events"]:
            kind = str(ev.get("kind", "")).replace("sup/", "")
            keys = ("rank", "pid", "rc", "by_supervisor", "attempt",
                    "nprocs", "delay_s")
            detail = " ".join(f"{k}={ev[k]}" for k in keys if k in ev)
            print(f"  {kind}: {detail}")
    print()
    print("skew timeline (slowest rank per aligned interval):")
    if not rep["skew_timeline"]:
        print("  (no aligned steps — single member or no overlap)")
    for run in rep["skew_timeline"]:
        span = (f"step {run['first']}" if run["first"] == run["last"]
                else f"steps {run['first']}-{run['last']}")
        print(f"  {span}: rank {run['slowest']} slowest "
              f"(max skew {run['skew_ms_max']:.1f}ms, "
              f"{run['rows']} row(s))")
    if rep.get("numerics_events"):
        print()
        print("cross-rank numerics divergence:")
        for ev in rep["numerics_events"]:
            print(f"  step {ev.get('step')}: grad_norm ratio "
                  f"{ev.get('ratio', 0.0):.1f}x "
                  f"[{ev.get('severity', '?')}] "
                  f"(rank {ev.get('max_rank')} vs rank "
                  f"{ev.get('min_rank')})")
    s = rep["summary"]
    if s:
        print()
        print(f"fleet summary: aligned_steps={s.get('aligned_steps')} "
              f"skew_p50={s.get('fleet_step_ms_skew_ms', 0.0):.1f}ms "
              f"({s.get('fleet_step_ms_skew_pct', 0.0):.1f}%) "
              f"wire_imbalance="
              f"{s.get('fleet_wire_bytes_imbalance', 0.0):.3f}")
        if s.get("straggler_rank") is not None:
            print(f"  STRAGGLER: rank {s['straggler_rank']} "
                  f"(score {s.get('straggler_score', 0.0):.2f}x median)")
        if s.get("unnoticed_deaths"):
            print(f"  UNNOTICED DEATHS: {s['unnoticed_deaths']}")
        if s.get("numerics_anomaly_total"):
            print(f"  numerics anomalies: "
                  f"{s['numerics_anomaly_total']} "
                  f"({s.get('numerics_critical_total', 0)} critical), "
                  f"grad_norm divergence "
                  f"{s.get('fleet_grad_norm_divergence', 0.0):.1f}x "
                  f"across ranks")


# -- trace mode (smtpu-trace/1 flight-recorder dumps) ---------------------
TRACE_SCHEMA_PREFIX = "smtpu-trace/"


def load_trace(path: str) -> dict:
    """Load one flight-recorder dump (obs/trace.py ``dump()`` output:
    a meta line + per-window records).  Crash tolerance matches
    :func:`load` — a truncated FINAL line is repair-parsed and counted
    under ``recovery``.  SystemExit(2) on unreadable / empty /
    not-a-trace input."""
    try:
        with open(path) as f:
            lines = [ln for ln in f if ln.strip()]
    except OSError as e:
        print(f"telemetry_report: cannot read {path}: {e}",
              file=sys.stderr)
        raise SystemExit(2)
    if not lines:
        print(f"telemetry_report: {path} is empty", file=sys.stderr)
        raise SystemExit(2)
    meta, windows = None, []
    recovered = dropped = 0
    last = len(lines) - 1
    for n, ln in enumerate(lines):
        try:
            rec = json.loads(ln)
        except ValueError:
            rec = repair_json_line(ln) if n == last else None
            if rec is None:
                dropped += 1
                continue
            rec["repaired"] = True
            recovered += 1
        if not isinstance(rec, dict):
            dropped += 1
            continue
        if rec.get("kind") == "meta":
            meta = rec
        elif rec.get("kind") == "trace/window":
            windows.append(rec)
    if meta is None and not windows:
        print(f"telemetry_report: {path} is not a trace dump "
              f"(no meta line, no trace/window records)",
              file=sys.stderr)
        raise SystemExit(2)
    schema = (meta or windows[0]).get("schema", "")
    if not str(schema).startswith(TRACE_SCHEMA_PREFIX):
        print(f"telemetry_report: {path} is not a trace dump "
              f"(schema={schema!r})", file=sys.stderr)
        raise SystemExit(2)
    windows.sort(key=lambda r: r.get("win", 0))
    return {"meta": meta or {"schema": schema, "synthesized": True},
            "windows": windows,
            "recovery": {"recovered": recovered, "dropped": dropped}}


def trace_report(doc: dict) -> dict:
    """Machine-shaped flight-recorder report: the per-window timeline
    (decision + why + volumes), decision counts, and the dump's hot-key
    attribution table."""
    rows = []
    decisions: Dict[str, int] = {}
    for rec in doc["windows"]:
        d = str(rec.get("decision", "?"))
        decisions[d] = decisions.get(d, 0) + 1
        row = {k: rec.get(k) for k in (
            "win", "step", "backend", "decision", "rows_in", "rows_out",
            "enc_bytes", "exchanges", "prices", "quant", "hot_rows",
            "ef_drained", "ef_rebanked", "shard_bytes", "repaired")
            if rec.get(k) is not None}
        rows.append(row)
    meta = doc["meta"]
    return {"meta": {k: meta.get(k) for k in
                     ("schema", "reason", "rank", "pid", "win", "step",
                      "records")},
            "windows": rows, "decisions": decisions,
            "hot_keys": meta.get("hot_keys") or [],
            "recovery": doc["recovery"]}


def _print_trace_report(rep: dict) -> None:
    m = rep["meta"]
    print(f"trace dump schema={m.get('schema')} reason={m.get('reason')} "
          f"rank={m.get('rank')} last_win={m.get('win')} "
          f"last_step={m.get('step')}")
    r = rep["recovery"]
    if r.get("recovered") or r.get("dropped"):
        print(f"crashed-dump recovery: {r.get('recovered', 0)} record(s) "
              f"repaired, {r.get('dropped', 0)} dropped")
    counts = " ".join(f"{k}={rep['decisions'][k]}"
                      for k in sorted(rep["decisions"]))
    print(f"windows: {len(rep['windows'])} ({counts})")
    print()
    for w in rep["windows"]:
        why = ""
        prices = w.get("prices") or {}
        if prices:
            why = "  priced: " + " ".join(
                f"{k}={_fmt_qty(v, 'B')}" for k, v in sorted(
                    prices.items(), key=lambda kv: kv[1]))
        extra = ""
        if w.get("hot_rows") is not None:
            extra += f" hot_rows={w['hot_rows']}"
        if w.get("ef_drained") is not None:
            extra += (f" ef_drained={w['ef_drained']:.4g}"
                      f" ef_rebanked={w.get('ef_rebanked', 0.0):.4g}")
        if w.get("repaired"):
            extra += " [repaired]"
        print(f"  win {w.get('win')} step {w.get('step')} "
              f"[{w.get('backend')}] {w.get('decision')}: "
              f"{w.get('rows_in')} -> {w.get('rows_out')} rows, "
              f"{_fmt_qty(w.get('enc_bytes'), 'B')} encoded"
              f"{extra}{why}")
    if rep["hot_keys"]:
        print()
        print("hot keys (touches / attributed wire bytes):")
        for h in rep["hot_keys"]:
            print(f"  key {h.get('key')}: {h.get('touches', 0.0):,.1f} "
                  f"touches, {_fmt_qty(h.get('bytes'), 'B')}")


# -- rendering ------------------------------------------------------------
def _print_numerics(num: dict) -> None:
    print()
    print("numerics health:")
    if not num["series"] and not num["anomalies"]:
        print("  (no numerics/* series — [obs] numerics off for this run)")
        return
    if num["series"]:
        w = max(len(r["series"]) for r in num["series"]) + 2
        print(f"  {'series'.ljust(w)}{'n':>6}{'mean':>12}{'min':>12}"
              f"{'max':>12}{'last':>12}")
        for r in num["series"]:
            print(f"  {r['series'].ljust(w)}{r['n']:>6}"
                  f"{r['mean']:>12.4g}{r['min']:>12.4g}"
                  f"{r['max']:>12.4g}{r['last']:>12.4g}")
    for key, v in sorted(num["counters"].items()):
        print(f"  {key}: {v:,.0f} (cumulative)")
    if num["nonfinite_total"]:
        print(f"  NONFINITE VALUES SEEN: {num['nonfinite_total']:,.0f}")
    sev = num["severities"]
    if not num["anomalies"]:
        print("  anomalies: none")
    else:
        counts = " ".join(f"{k}={sev[k]}" for k in sorted(sev))
        print(f"  anomalies: {len(num['anomalies'])} ({counts})")
        for a in num["anomalies"]:
            detail = ""
            if a.get("baseline") is not None:
                detail += f" baseline={a['baseline']:.4g}"
            if a.get("z") is not None:
                detail += f" z={a['z']:.1f}"
            val = a.get("value")
            val_s = f"{val:.4g}" if isinstance(val, (int, float)) else val
            print(f"    step {a['step']}: {a['anomaly']} "
                  f"[{a['severity']}] {a.get('series')}="
                  f"{val_s}{detail}")


def _fmt_qty(v, unit="") -> str:
    if v is None:
        return "-"
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"),
                          (1e3, "K")):
        if abs(v) >= scale:
            return f"{v / scale:.2f}{suffix}{unit}"
    return f"{v:.0f}{unit}"


def _print_compile(comp: dict) -> None:
    print()
    print("compile catalog:")
    if not comp["fns"]:
        print("  (no compile/* series — [obs] costs off for this run)")
        return
    w = max(len(n) for n in comp["fns"]) + 2
    print(f"  {'fn'.ljust(w)}{'compiles':>9}{'retraces':>9}"
          f"{'compile_ms':>12}{'flops':>10}{'bytes':>10}"
          f"{'peak':>10}{'drift':>14}")
    for name in sorted(comp["fns"]):
        r = comp["fns"][name]
        drift = ""
        if r.get("flops_drift_pct") is not None:
            drift += f"f{r['flops_drift_pct']:+.1f}%"
        if r.get("bytes_drift_pct") is not None:
            drift += f" b{r['bytes_drift_pct']:+.1f}%"
        print(f"  {name.ljust(w)}{r['compiles']:>9}{r['retraces']:>9}"
              f"{r['compile_ms']:>12.1f}"
              f"{_fmt_qty(r.get('flops')):>10}"
              f"{_fmt_qty(r.get('bytes')):>10}"
              f"{_fmt_qty(r.get('peak_bytes')):>10}"
              f"{drift or '-':>14}")
    print(f"  total: {comp['compile_ms_total']:.1f}ms compiling, "
          f"{comp['retraces_total']} retrace(s)")
    if comp["retraces_total"]:
        print("  RETRACES SEEN: a compiled program re-traced — look for "
              "shape/dtype churn on the fns above")
    if comp["captures"]:
        print("  profile captures:")
        for c in comp["captures"]:
            print(f"    step {c.get('start_step')}: {c.get('steps')} "
                  f"step(s) [{c.get('reason')}] -> {c.get('run_dir')} "
                  f"({c.get('events')} trace event(s))")


def _print_report(rep: dict) -> None:
    m = rep["meta"]
    print(f"run={m.get('run')} ident={m.get('ident')} "
          f"schema={m.get('schema')}")
    if "recovery" in rep:
        r = rep["recovery"]
        print(f"crashed-run recovery: {r.get('recovered', 0)} record(s) "
              f"repaired, {r.get('dropped', 0)} dropped")
    print()
    print("phase latency (ms):")
    if not rep["phases"]:
        print("  (no histograms recorded — telemetry off or no spans "
              "crossed a step boundary)")
    else:
        w = max(len(r["phase"]) for r in rep["phases"]) + 2
        print(f"  {'phase'.ljust(w)}{'n':>7}{'mean':>9}{'p50':>9}"
              f"{'p95':>9}{'p99':>9}{'total':>11}")
        for r in rep["phases"]:
            print(f"  {r['phase'].ljust(w)}{r['n']:>7}"
                  f"{r['mean_ms']:>9.3f}{r['p50_ms']:>9.3f}"
                  f"{r['p95_ms']:>9.3f}{r['p99_ms']:>9.3f}"
                  f"{r['total_ms']:>11.1f}")
    if "wire_timeline" in rep:
        print()
        print("wire-format decisions:")
        if not rep["wire_timeline"]:
            print("  (no window push counters — single-step push or "
                  "traffic counting off)")
        for run in rep["wire_timeline"]:
            span = (f"step {run['first']}" if run["first"] == run["last"]
                    else f"steps {run['first']}-{run['last']}")
            print(f"  {span}: {run['decision']} "
                  f"({run['windows']} record(s))")
        # hot-plane collective decision mix (ISSUE 19), next to the
        # wire-format ladder it extends: which collective the plan
        # picked per window, per backend, with the booked byte delta
        coll = {
            b: {k: v for k, v in m.items()
                if k.startswith("collective_")
                or k == "hot_psum_bytes_saved"}
            for b, m in (rep.get("traffic", {}).get("transfer")
                         or {}).items()}
        coll = {b: m for b, m in coll.items()
                if any(k.startswith("collective_") for k in m)}
        if coll:
            print()
            print("collective decisions (hot plane / dense rung):")
            for b, m in sorted(coll.items()):
                saved = m.get("hot_psum_bytes_saved", 0.0)
                print(f"  {b}: psum={m.get('collective_psum', 0):g} "
                      f"sparse_ar={m.get('collective_sparse_ar', 0):g}"
                      + (f" ({saved:,.0f} B saved vs dense)"
                         if saved else ""))
    if "decisions" in rep:
        print()
        print("control decisions:")
        c = rep.get("control") or {}
        if not rep["decisions"]:
            hint = (" (no evaluations — control off)"
                    if not c.get("evaluations") else
                    f" over {c.get('evaluations', 0)} evaluation(s)")
            print(f"  (none){hint}")
        else:
            print(f"  {c.get('evaluations', 0)} evaluations, "
                  f"{c.get('decisions', 0)} decisions, "
                  f"{c.get('applied', 0)} applied "
                  f"({c.get('decisions_per_1k_steps', 0.0):.2f}/1k steps)")
            for d in rep["decisions"]:
                ev = d["evidence"]
                ev_s = ", ".join(f"{k}={v:.4g}" if isinstance(v, float)
                                 else f"{k}={v}"
                                 for k, v in sorted(ev.items())
                                 if not isinstance(v, (dict, list)))
                print(f"  step {d['step']}: {d['knob']} {d['action']} "
                      f"{d['old']} -> {d['new']} "
                      f"(win={d['win']:.4f}, streak={d['streak']})")
                if ev_s:
                    print(f"      evidence: {ev_s}")
    if "numerics" in rep:
        _print_numerics(rep["numerics"])
    if "compile" in rep:
        _print_compile(rep["compile"])
    if "traffic" in rep:
        t = rep["traffic"]
        print()
        print(f"traffic over {t['steps']} step(s):")
        for backend in sorted(t["transfer"]):
            print(f"  backend={backend}:")
            for k, v in sorted(t["transfer"][backend].items()):
                per = t.get("per_step", {}).get(backend, {}).get(k)
                extra = f"  ({per:,.1f}/step)" if per is not None else ""
                print(f"    {k}: {v:,.0f}{extra}")
        if t["train"]:
            print("  train:")
            for k, v in sorted(t["train"].items()):
                print(f"    {k}: {v:,.1f}")
        if "stall_ms_per_step" in t:
            print(f"  stall_ms_per_step: {t['stall_ms_per_step']:.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="per-phase latency, wire-format timeline and "
                    "traffic summary from a telemetry JSONL")
    ap.add_argument("path", help="telemetry.jsonl from obs.StepRecorder "
                    "(or, with --fleet, a merged fleet.jsonl / a fleet "
                    "directory)")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON instead of text")
    ap.add_argument("--phases-only", action="store_true",
                    help="only the per-phase latency table")
    ap.add_argument("--numerics", action="store_true",
                    help="only the numerics-health section: numerics/* "
                    "series stats, nonfinite totals and the anomaly "
                    "timeline (smtpu-numerics/1 events)")
    ap.add_argument("--pull", dest="pull_only", action="store_true",
                    help="only the delta-pull plane section: per-"
                    "backend cache hit ratio, pull decision mix and "
                    "the bytes-saved timeline (transfer/pull_* series)")
    ap.add_argument("--compile", dest="compile_only",
                    action="store_true",
                    help="only the compile-catalog section: per-fn "
                    "compile/retrace/compile_ms, XLA flops/bytes and "
                    "profile-capture timeline (compile/* series)")
    ap.add_argument("--catalog", default=None, metavar="JSON",
                    help="a runs/compile_catalog.json (smtpu-costs/1) "
                    "to merge hand-model drift columns from")
    ap.add_argument("--fleet", action="store_true",
                    help="treat path as an smtpu-fleet/1 merged "
                    "timeline (or a fleet dir): per-rank columns, "
                    "supervisor events, skew timeline")
    ap.add_argument("--trace", action="store_true",
                    help="treat path as an smtpu-trace/1 flight-"
                    "recorder dump (obs/trace.py): per-window wire "
                    "decisions with priced alternatives, hot keys")
    args = ap.parse_args(argv)

    if args.trace:
        rep = trace_report(load_trace(args.path))
        if args.json:
            json.dump(rep, sys.stdout, indent=2)
            print()
        else:
            _print_trace_report(rep)
        return 0
    if args.fleet:
        rep = fleet_report(load_fleet(args.path))
        if args.json:
            json.dump(rep, sys.stdout, indent=2)
            print()
        else:
            _print_fleet_report(rep)
        return 0
    catalog = None
    if args.catalog:
        try:
            with open(args.catalog) as f:
                catalog = json.load(f)
        except (OSError, ValueError) as e:
            print(f"telemetry_report: cannot read catalog "
                  f"{args.catalog}: {e}", file=sys.stderr)
            raise SystemExit(2)
        if not str(catalog.get("schema", "")).startswith("smtpu-costs/"):
            print(f"telemetry_report: {args.catalog} is not a cost "
                  f"catalog (schema={catalog.get('schema')!r})",
                  file=sys.stderr)
            raise SystemExit(2)
    if args.numerics:
        doc = load(args.path)
        num = numerics_summary(doc)
        if args.json:
            json.dump({"meta": doc["meta"], "numerics": num},
                      sys.stdout, indent=2)
            print()
        else:
            m = doc["meta"]
            print(f"run={m.get('run')} ident={m.get('ident')} "
                  f"schema={m.get('schema')}")
            _print_numerics(num)
        return 0
    if args.pull_only:
        doc = load(args.path)
        pull = pull_summary(doc)
        if args.json:
            json.dump({"meta": doc["meta"], "pull": pull},
                      sys.stdout, indent=2)
            print()
        else:
            m = doc["meta"]
            print(f"run={m.get('run')} ident={m.get('ident')} "
                  f"schema={m.get('schema')}")
            _print_pull(pull)
        return 0
    if args.compile_only:
        doc = load(args.path)
        comp = compile_summary(doc, catalog=catalog)
        if args.json:
            json.dump({"meta": doc["meta"], "compile": comp},
                      sys.stdout, indent=2)
            print()
        else:
            m = doc["meta"]
            print(f"run={m.get('run')} ident={m.get('ident')} "
                  f"schema={m.get('schema')}")
            _print_compile(comp)
        return 0
    rep = report(load(args.path), phases_only=args.phases_only,
                 catalog=catalog)
    if args.json:
        json.dump(rep, sys.stdout, indent=2)
        print()
    else:
        _print_report(rep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
