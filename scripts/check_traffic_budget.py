#!/usr/bin/env python
"""Traffic-budget regression gate over two bench JSONs.

The window-coalesced push exists to cut wire traffic; this script makes
that a *checked* property instead of a one-time measurement.  It reads
two bench result files (``{"ts": ..., "result": {cell: {metric:
value}}}``: the cell records of the yardstick PR 29 deleted; nothing in
the tree writes that format any more, ROADMAP D6), lines up every
cell present in both, and fails when a traffic metric regressed beyond
tolerance:

    python scripts/check_traffic_budget.py baseline.json candidate.json
    python scripts/check_traffic_budget.py base.json cand.json \
        --tolerance 0.05 --cells w2v_1m_window,w2v_1m_hybrid

Either side may also be a **telemetry JSONL** from a live run
(``obs.StepRecorder`` output, schema ``smtpu-telemetry/1``): the stream
is aggregated to one cell named after its run (``run=word2vec`` ->
cell ``word2vec``) with the same per-step metrics, so a production
run's wire traffic can be gated against a bench baseline — or against
yesterday's run — with the identical tolerance logic::

    python scripts/check_traffic_budget.py baseline.json telemetry.jsonl

Traffic metrics are lower-is-better wire/dispatch counters
(``wire_bytes_per_step``, ``dispatches_per_step``,
``dispatches_per_window``) plus the input pipeline's host-stall split
(``stall_ms_per_step`` — the number the asynchronous input pipeline
exists to hold at ~0); cells without them (pure throughput cells) are
skipped.  Timing metrics carry an absolute noise floor: a stall
"regression" of 60µs/step is scheduler jitter, not a lost overlap, so
the gate only fires when the increase clears BOTH the relative
tolerance and the floor.  Exit codes: 0 within budget, 1 regression,
2 usage / unreadable input.  ``scripts/run_tier1.sh`` runs this
advisorily when ``BENCH_BASELINE``/``BENCH_CANDIDATE`` point at files —
the tier-1 verdict stays pytest's, but the regression is printed next
to it.
"""

from __future__ import annotations

import argparse
import json
import sys

#: lower-is-better counters the budget covers, with the detail fields
#: printed for context when a covered cell is reported.  The serving
#: plane's serve_qps cell gates on its tail latency (serve_p99_ms) and
#: on hit-ratio REGRESSION via the lower-is-better complement
#: serve_miss_ratio; pull_bytes_per_step budgets the pull-side wire
#: ledger the same way wire_bytes_per_step budgets pushes.
TRAFFIC_METRICS = ("wire_bytes_per_step", "dispatches_per_step",
                   "dispatches_per_window", "stall_ms_per_step",
                   "serve_p99_ms", "serve_miss_ratio",
                   "pull_bytes_per_step", "control_decisions_per_1k_steps",
                   "fleet_step_ms_skew_pct", "fleet_wire_bytes_imbalance",
                   "ef_mass_growth", "fleet_grad_norm_divergence",
                   # snapshot-shipping wire cost (ISSUE 17): mean
                   # encoded bytes per steady-state delta publish on
                   # the serve_fleet cell — the number the shared
                   # transfer/delta.py codec exists to hold down.  An
                   # exact byte model, so no noise floor.
                   "delta_bytes_per_publish",
                   # hot-plane reconcile wire under whichever collective
                   # each window's plan picked (ISSUE 19): the number
                   # the sparse allreduce exists to hold down.  An
                   # exact byte model (transfer/sparse_allreduce.py),
                   # so no noise floor.
                   "hot_psum_bytes_per_step")
DETAIL_METRICS = ("window_sparse", "window_dense", "window_fmt_dense",
                  "window_fmt_sparse", "window_fmt_q",
                  "window_fmt_bitmap", "window_fmt_sketch",
                  "wire_quant", "wire_sketch",
                  "plan_compiles", "plan_cache_hits", "coalesce_ratio",
                  "push_window", "host_stall_ms", "queue_depth",
                  "pipeline", "speedup_vs_off", "qps", "p50_ms",
                  "hit_ratio", "streams", "snapshots",
                  "staleness_bound_steps", "pull_hot_rows",
                  "pull_cache_hits", "pull_delta_rows",
                  "pull_bytes_saved", "pull_fmt_full", "pull_fmt_bf16",
                  "pull_fmt_q", "pull_quant", "pull_cache",
                  "pull_reduction_x",
                  "control_applied", "control_evaluations",
                  "steps_to_reconverge", "recompiles", "hot_k",
                  "straggler_rank", "members_dead", "unnoticed_deaths",
                  "fleet_restarts", "aligned_steps",
                  "fleet_epoch", "fleet_reconverge_steps",
                  "migration_bytes",
                  "numerics_anomalies", "numerics_critical",
                  "numerics_nonfinite", "cross_rank_anomalies",
                  "retraces", "compile_ms", "peak_hbm_bytes",
                  "serve_fleet_qps", "qps_scaling_x", "delta_publishes",
                  "full_publishes", "delta_vs_full_ratio",
                  "delta_fmt_mix", "staleness_s", "gates_pass",
                  "collective", "collective_psum", "collective_sparse_ar",
                  "hot_psum_bytes_saved_per_step", "hot_psum_reduction_x",
                  "seeded_touched_fraction", "parity_ok",
                  "tail_bit_identical")
#: absolute increase a metric must clear before it can regress: wall-
#: clock metrics jitter run to run while the counter metrics are exact,
#: so only the former get a floor (ms for the stall split;
#: serve_p99_ms is one tail sample under deliberate train/serve
#: contention — the stall gate's 0.1ms convention applies; a
#: miss-ratio wiggle under 1 point is query-stream sampling noise)
ABS_NOISE_FLOOR = {"stall_ms_per_step": 0.1, "serve_p99_ms": 0.1,
                   "serve_miss_ratio": 0.01,
                   # a quiet baseline (0 decisions) must tolerate the
                   # occasional legitimate retune; only a flapping tuner
                   # (> 2 decisions per 1k steps above baseline) fails
                   "control_decisions_per_1k_steps": 2.0,
                   # cross-rank skew is OS-scheduler wall-clock noise on
                   # the shared dev host the fleet smoke runs on; only a
                   # persistent straggler-scale widening (> 15 points of
                   # the median step time) is a real fleet regression,
                   # and a wire-imbalance wobble under 0.2 (max/mean-1)
                   # is batch-composition variance, not a placement bug
                   "fleet_step_ms_skew_pct": 15.0,
                   "fleet_wire_bytes_imbalance": 0.2,
                   # error-feedback residual mass drifts with batch
                   # composition; only a sustained growth factor (> 0.5
                   # above baseline's last/mean ratio) is a compounding-
                   # quantization-error signal worth failing on, and a
                   # cross-rank grad-norm spread under 2x is ordinary
                   # hot/tail sampling asymmetry between ranks
                   "ef_mass_growth": 0.5,
                   "fleet_grad_norm_divergence": 2.0}


def load_telemetry_cells(path: str) -> dict:
    """Aggregate a StepRecorder JSONL into one bench-shaped cell keyed
    by the run name.  Counters are summed across backends (the gate
    budgets the run's total wire, not the split) and normalized by the
    recorded step count; window decision totals ride along as detail."""
    from telemetry_report import (control_summary, load,
                                  numerics_summary, parse_series_key,
                                  phase_table, traffic_summary)

    doc = load(path)     # SystemExit(2) on unreadable/bad schema
    t = traffic_summary(doc)
    steps = max(t["steps"], 1)
    wire = sum(m.get("wire_bytes", 0.0) for m in t["transfer"].values())
    disp = sum(m.get("dispatches", 0.0) for m in t["transfer"].values())
    cell: dict = {}
    if wire:
        cell["wire_bytes_per_step"] = wire / steps
    if disp:
        cell["dispatches_per_step"] = disp / steps
    pull = sum(m.get("pull_bytes", 0.0) for m in t["transfer"].values())
    if pull:
        cell["pull_bytes_per_step"] = pull / steps
    if "stall_ms_per_step" in t:
        cell["stall_ms_per_step"] = t["stall_ms_per_step"]
    for decision in ("window_sparse", "window_dense", "window_fmt_dense",
                     "window_fmt_sparse", "window_fmt_q",
                     "window_fmt_bitmap", "window_fmt_sketch",
                     "plan_compiles", "plan_cache_hits",
                     # delta-pull plane (ISSUE 20): decision mix + cache
                     # effectiveness ride as detail next to the
                     # pull_bytes_per_step gate metric
                     "pull_fmt_full", "pull_fmt_bf16", "pull_fmt_q",
                     "pull_cache_hits", "pull_delta_rows",
                     "pull_bytes_saved"):
        total = sum(m.get(decision, 0.0) for m in t["transfer"].values())
        if total:
            cell[decision] = total
    hot_pulls = sum(m.get("pull_hot_rows", 0.0)
                    for m in t["transfer"].values())
    if hot_pulls:
        cell["pull_hot_rows"] = hot_pulls
    # control plane: gate on the decision rate (a flapping tuner is a
    # regression even when each individual decision looks justified);
    # absent entirely when the run never evaluated (control off), so a
    # control-off baseline never blocks a control-on candidate
    ctl = control_summary(doc)
    if ctl.get("evaluations"):
        cell["control_decisions_per_1k_steps"] = \
            ctl.get("decisions_per_1k_steps", 0.0)
        cell["control_applied"] = ctl["applied"]
        cell["control_evaluations"] = ctl["evaluations"]
    # numerics health plane (obs/numerics.py): nonfinite/critical are
    # hard candidate-side gates (numerics_violations); the EF residual
    # growth factor (last/mean of the worst field) is advisory — a
    # lower-is-better tolerance metric, absent when numerics was off so
    # a numerics-off baseline never blocks a numerics-on candidate
    num = numerics_summary(doc)
    if num["series"] or num["anomalies"]:
        cell["numerics_anomalies"] = len(num["anomalies"])
        cell["numerics_critical"] = num["severities"].get("critical", 0)
        cell["numerics_nonfinite"] = num["nonfinite_total"]
        growth = 0.0
        for row in num["series"]:
            if parse_series_key(row["series"])[0] == "numerics/ef_mass":
                growth = max(growth,
                             row["last"] / max(row["mean"], 1e-12))
        if growth:
            cell["ef_mass_growth"] = growth
    # compiler-cost plane (obs/costs.py): steady-state retrace count is
    # a hard candidate-side gate (retrace_violations); compile_ms and
    # the peak live-at-once HBM bound are advisory detail cells.  All
    # absent when [obs] costs was off, so a costs-off baseline never
    # blocks a costs-on candidate
    retraces = compile_ms = 0.0
    peak = 0.0
    saw_compile = False
    if doc["summary"] is not None:
        totals = doc["summary"].get("counters") or {}
    else:
        totals = {}
        for rec in doc["steps"]:
            for key, delta in (rec.get("counters") or {}).items():
                totals[key] = totals.get(key, 0.0) + delta
    for key, v in totals.items():
        name = parse_series_key(key)[0]
        if name == "compile/retraces":
            retraces += float(v)
            saw_compile = True
        elif name == "compile/compile_ms":
            compile_ms += float(v)
            saw_compile = True
        elif name == "compile/compiles":
            saw_compile = True
    for rec in doc["steps"]:
        for key, v in (rec.get("gauges") or {}).items():
            if parse_series_key(key)[0] == "compile/peak_bytes":
                peak = max(peak, float(v))
    if saw_compile:
        cell["retraces"] = retraces
        cell["compile_ms"] = compile_ms
        if peak:
            cell["peak_hbm_bytes"] = peak
    # wire-trace plane (obs/trace.py): the per-step latency mean plus
    # the tracer's volume counters — the trace-overhead advisory diffs
    # step_ms between a trace-off baseline and a trace-on candidate
    for row in phase_table(doc):
        if row["phase"] == "step_ms":
            cell["step_ms"] = row["mean_ms"]
    for tkey in ("trace/windows", "trace/records", "trace/dumps"):
        total = sum(float(v) for k, v in totals.items()
                    if parse_series_key(k)[0] == tkey)
        if total:
            cell[tkey.replace("/", "_")] = total
    run = str(doc["meta"].get("run", "telemetry"))
    return {run: cell} if cell else {}


def load_fleet_cells(path: str) -> dict:
    """Aggregate a merged ``smtpu-fleet/1`` timeline (obs.FleetCollector
    output) into one bench-shaped cell keyed by the fleet run name: the
    skew/imbalance gate metrics plus the health details the
    unnoticed-death hard gate reads."""
    from telemetry_report import load_fleet

    doc = load_fleet(path)   # SystemExit(2) on unreadable/bad schema
    s = doc.get("summary")
    if not s:
        return {}
    health = s.get("health") or {}
    cell = {
        "fleet_step_ms_skew_pct": float(
            s.get("fleet_step_ms_skew_pct", 0.0)),
        "fleet_wire_bytes_imbalance": float(
            s.get("fleet_wire_bytes_imbalance", 0.0)),
        "aligned_steps": s.get("aligned_steps", 0),
        "members_dead": sum(1 for v in health.values() if v == "dead"),
        "fleet_restarts": sum((s.get("restarts") or {}).values()),
        "unnoticed_deaths": len(s.get("unnoticed_deaths") or ()),
    }
    if s.get("straggler_rank") is not None:
        cell["straggler_rank"] = s["straggler_rank"]
    if s.get("fleet_epoch") is not None:
        # elastic membership plane (ISSUE 16): how far the epoch moved,
        # how long the fleet took to agree on the final membership, and
        # what the migrations cost in modeled delta bytes — advisory
        # context next to the skew/imbalance gates
        cell["fleet_epoch"] = int(s["fleet_epoch"])
        if s.get("fleet_reconverge_steps") is not None:
            cell["fleet_reconverge_steps"] = int(
                s["fleet_reconverge_steps"])
        cell["migration_bytes"] = int(s.get("migration_bytes", 0))
    if s.get("numerics_anomaly_total") is not None:
        cell["numerics_anomalies"] = int(s["numerics_anomaly_total"])
        cell["numerics_critical"] = int(
            s.get("numerics_critical_total", 0))
        cell["fleet_grad_norm_divergence"] = float(
            s.get("fleet_grad_norm_divergence", 0.0))
        cell["cross_rank_anomalies"] = int(
            s.get("cross_rank_anomalies", 0))
    run = str(doc["meta"].get("run", "fleet"))
    return {run: cell}


def _sniff_schema(path: str, prefix: str) -> bool:
    """Content, not file extension, decides (bench caches are also
    .json): does the first line carry the given schema tag?"""
    try:
        with open(path) as f:
            head = json.loads(f.readline() or "null")
        return isinstance(head, dict) and str(
            head.get("schema", "")).startswith(prefix)
    except (OSError, ValueError):
        return False


def _is_telemetry(path: str) -> bool:
    return _sniff_schema(path, "smtpu-telemetry/")


def _is_fleet(path: str) -> bool:
    return _sniff_schema(path, "smtpu-fleet/")


def load_cells(path: str) -> dict:
    if _is_fleet(path):
        return load_fleet_cells(path)
    if _is_telemetry(path):
        return load_telemetry_cells(path)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        print(f"check_traffic_budget: cannot read {path}: {e}",
              file=sys.stderr)
        raise SystemExit(2)
    cells = doc.get("result", doc)
    if not isinstance(cells, dict):
        print(f"check_traffic_budget: {path} has no result cells",
              file=sys.stderr)
        raise SystemExit(2)
    return {c: m for c, m in cells.items() if isinstance(m, dict)}


def compare(base: dict, cand: dict, tolerance: float,
            only_cells=None) -> list:
    """Return [(cell, metric, base, cand, rel_change)] regressions."""
    regressions = []
    for cell in sorted(set(base) & set(cand)):
        if only_cells and cell not in only_cells:
            continue
        for metric in TRAFFIC_METRICS:
            b, c = base[cell].get(metric), cand[cell].get(metric)
            if b is None or c is None:
                continue
            b, c = float(b), float(c)
            if c - b <= ABS_NOISE_FLOOR.get(metric, 0.0):
                continue
            if b <= 0:
                # a zero baseline (e.g. a pre-staged cell's stall, or a
                # pipelined stall measured at ~0) regresses on ANY
                # above-floor increase — rel change is undefined there
                regressions.append((cell, metric, b, c, float("inf")))
                continue
            rel = (c - b) / b
            if rel > tolerance:
                regressions.append((cell, metric, b, c, rel))
    return regressions


def decision_mix_violations(cells: dict) -> list:
    """Cells that claim wire compression is on (``wire_quant`` not
    ``off``, or ``wire_sketch`` truthy) and booked window decisions, yet
    never once chose an encoded format — the calibration equivalent of a
    feature flag that silently no-ops.  Such a cell means the crossover
    model and the live traffic disagree so badly the armed rung never
    fires, which is a gate failure, not a tuning preference."""
    bad = []
    fmt_keys = ("window_fmt_dense", "window_fmt_sparse",
                "window_fmt_q", "window_fmt_bitmap",
                "window_fmt_sketch")
    for cell, m in sorted(cells.items()):
        quant = m.get("wire_quant")
        sketch = m.get("wire_sketch")
        armed = quant not in (None, "off") or bool(sketch)
        if not armed:
            continue
        total = sum(float(m.get(k, 0.0)) for k in fmt_keys)
        encoded = float(m.get("window_fmt_q", 0.0)) \
            + float(m.get("window_fmt_bitmap", 0.0)) \
            + float(m.get("window_fmt_sketch", 0.0))
        if total > 0 and encoded <= 0:
            knob = quant if quant not in (None, "off") else "sketch"
            bad.append((cell, knob, total))
    return bad


def pull_mix_violations(cells: dict) -> list:
    """The armed-but-dead guard for the delta-pull plane (ISSUE 20),
    same pattern as the wire-compression and collective mixes: a cell
    that claims a pull knob is on yet shows zero evidence the feature
    ever fired is a gate failure, not a tuning preference.  Two forms:

    * ``pull_quant`` armed (not ``off``) with pull decisions booked but
      zero encoded picks — the pricing guard never let the quantized
      rung win, so the knob silently no-ops;
    * ``pull_cache`` armed (truthy line count) with pull decisions
      booked but zero cache hits — on any workload with repeated keys
      (every cell we gate runs a Zipf stream) a dead cache means the
      version plane or the watermark protocol is broken.
    """
    bad = []
    fmt_keys = ("pull_fmt_full", "pull_fmt_bf16", "pull_fmt_q")
    for cell, m in sorted(cells.items()):
        total = sum(float(m.get(k, 0.0)) for k in fmt_keys)
        quant = m.get("pull_quant")
        if quant not in (None, "off") and total > 0:
            encoded = float(m.get("pull_fmt_bf16", 0.0)) \
                + float(m.get("pull_fmt_q", 0.0))
            if encoded <= 0:
                bad.append((cell, f"pull_quant={quant}",
                            f"{total:g} pull decisions but zero "
                            "bf16/sparse_q picks"))
        if m.get("pull_cache") and total > 0 \
                and float(m.get("pull_cache_hits", 0.0)) <= 0:
            bad.append((cell, f"pull_cache={m['pull_cache']}",
                        f"{total:g} pull decisions but zero cache "
                        "hits"))
    return bad


def collective_mix_violations(cells: dict) -> list:
    """Cells that armed the hot-plane collective ladder (``collective``
    not ``psum``) and booked collective decisions, yet never once chose
    the sparse allreduce — the decision-mix pattern applied to ISSUE
    19's ladder: the sparsear cell runs at the Zipf(1.0) validation
    shape where the touched-fraction crossover MUST price the sparse
    exchange below the dense psum, so an armed ``auto`` that sits on
    psum there means the density seeding and the live traffic disagree
    badly enough that the feature silently no-ops — a gate failure,
    not a tuning preference."""
    bad = []
    for cell, m in sorted(cells.items()):
        mode = m.get("collective")
        if mode in (None, "psum"):
            continue
        total = float(m.get("collective_psum", 0.0)) \
            + float(m.get("collective_sparse_ar", 0.0))
        if total > 0 and float(m.get("collective_sparse_ar", 0.0)) <= 0:
            bad.append((cell, mode, total))
    return bad


def fleet_violations(cells: dict) -> list:
    """Candidate cells where a member died UNNOTICED — heartbeat gap
    says dead, supervisor log has no exit event.  That is not a
    performance number to tolerance-check; it means the fleet lost a
    rank and the observability layer was the only thing that caught it,
    so the run fails outright (the decision-mix pattern: a hard
    candidate-side property, not a baseline comparison)."""
    bad = []
    for cell, m in sorted(cells.items()):
        n = m.get("unnoticed_deaths")
        if n is not None and float(n) > 0:
            bad.append((cell, int(n)))
    return bad


def numerics_violations(cells: dict) -> list:
    """Candidate cells whose run produced nonfinite values or a
    critical numerics anomaly (obs/numerics.py).  A NaN in the
    parameter table or a critical-severity health event is not a
    performance number to tolerance-check — the training run is
    numerically broken regardless of how the baseline looked, so it
    fails outright (the unnoticed-death pattern: a hard candidate-side
    property, not a comparison)."""
    bad = []
    for cell, m in sorted(cells.items()):
        nonfin = float(m.get("numerics_nonfinite", 0) or 0)
        crit = float(m.get("numerics_critical", 0) or 0)
        if nonfin > 0 or crit > 0:
            bad.append((cell, int(nonfin), int(crit)))
    return bad


def retrace_violations(base: dict, cand: dict) -> list:
    """Candidate cells whose steady-state retrace count exceeds the
    baseline's (floor 1: one late retrace — a tail batch, a control
    safe-point — is tolerated even against a zero baseline).  A retrace
    storm multiplies step latency by compile time regardless of how the
    wire counters look, so it fails against the BASELINE count rather
    than tolerance-scaling: retraces are exact integers, not noisy
    measurements.  Cells where the candidate lacks the metric (costs
    off) are skipped."""
    bad = []
    for cell in sorted(set(base) & set(cand)):
        c = cand[cell].get("retraces")
        if c is None:
            continue
        b = float(base[cell].get("retraces", 0.0) or 0.0)
        if float(c) > max(b, 1.0):
            bad.append((cell, b, float(c)))
    return bad


def trace_dump_violations(pattern: str) -> list:
    """Crash dumps (``smtpu-trace/1`` flight-recorder files, obs/trace.py)
    that exist but cannot be parsed even after single-line repair.  A
    dump is written precisely because something went wrong; a dump that
    is schema-invalid or truncated beyond repair means the flight
    recorder failed at its one job, so its presence fails the gate
    outright (the unnoticed-death pattern: a hard candidate-side
    property).  A dump that parses — even with its final line repaired,
    even with zero window records (crash before the first window) — is
    healthy.  Returns [(path, reason)]."""
    import contextlib
    import glob as _glob
    import io

    from telemetry_report import load_trace

    bad = []
    for path in sorted(_glob.glob(pattern)):
        try:
            with contextlib.redirect_stderr(io.StringIO()) as err:
                load_trace(path)
        except SystemExit:
            reason = err.getvalue().strip() or \
                "schema-invalid or truncated beyond repair"
            bad.append((path, reason.splitlines()[-1]))
    return bad


def trace_overhead_report(base: dict, cand: dict, bound: float) -> list:
    """Advisory step-latency cost of the wire tracer: cells where the
    candidate ran with tracing armed (``trace_windows`` counter present)
    against a trace-off baseline, compared on the step_ms mean.  Returns
    [(cell, base_ms, cand_ms, rel, over_bound)] — printed next to the
    verdict, never failing it: step_ms wall-clock jitters run to run,
    and the hard bit-identity guarantee is pytest's (test_trace.py), not
    this gate's."""
    rows = []
    for cell in sorted(set(base) & set(cand)):
        b_ms = base[cell].get("step_ms")
        c_ms = cand[cell].get("step_ms")
        if b_ms is None or c_ms is None:
            continue
        if not cand[cell].get("trace_windows") \
                or base[cell].get("trace_windows"):
            continue
        b_ms, c_ms = float(b_ms), float(c_ms)
        rel = (c_ms - b_ms) / b_ms if b_ms > 0 else 0.0
        rows.append((cell, b_ms, c_ms, rel, rel > bound))
    return rows


def serve_qps_report(base: dict, cand: dict, bound: float) -> list:
    """Advisory aggregate-throughput report for serving cells: the one
    HIGHER-is-better number in the budget (``serve_fleet_qps``, the
    serve_fleet cell's N-replica aggregate), so it cannot ride the
    lower-is-better compare() path.  A drop past ``bound`` prints
    loudly next to the verdict but never fails the gate — qps on the
    shared bench host is wall-clock (scheduler-jittered), and the hard
    serving gates are the exact-byte delta_bytes_per_publish and the
    floor-protected serve_p99_ms.  Returns
    [(cell, base_qps, cand_qps, rel, over_bound)]."""
    rows = []
    for cell in sorted(set(base) & set(cand)):
        b = base[cell].get("serve_fleet_qps")
        c = cand[cell].get("serve_fleet_qps")
        if b is None or c is None:
            continue
        b, c = float(b), float(c)
        rel = (c - b) / b if b > 0 else 0.0
        rows.append((cell, b, c, rel, -rel > bound))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="fail when bench traffic counters regressed")
    ap.add_argument("baseline", help="baseline bench JSON")
    ap.add_argument("candidate", help="candidate bench JSON")
    ap.add_argument("--tolerance", type=float, default=0.10,
                    help="allowed relative increase (default 0.10)")
    ap.add_argument("--cells", default=None,
                    help="comma-separated cell allowlist (default: every "
                         "cell present in both files)")
    ap.add_argument("--trace-dumps", default=None, metavar="GLOB",
                    help="glob of flight-recorder crash dumps "
                         "(runs/trace_r*_p*.jsonl); any matching dump "
                         "that is schema-invalid or truncated beyond "
                         "repair fails the gate")
    ap.add_argument("--trace-overhead-bound", type=float, default=0.05,
                    help="advisory step_ms bound for a trace-on "
                         "candidate vs a trace-off baseline "
                         "(default 0.05; never fails the gate)")
    args = ap.parse_args(argv)

    if args.trace_dumps:
        dumps = trace_dump_violations(args.trace_dumps)
        if dumps:
            print("TRACE DUMP UNREADABLE:")
            for path, reason in dumps:
                print(f"  {path}: {reason} — the flight recorder's "
                      "crash dump cannot be replayed")
            return 1

    base = load_cells(args.baseline)
    cand = load_cells(args.candidate)
    only = set(args.cells.split(",")) if args.cells else None
    if only:
        missing = sorted(only - (set(base) & set(cand)))
        if missing:
            print("check_traffic_budget: requested cells absent from "
                  "one side: " + ", ".join(missing), file=sys.stderr)
            return 2

    covered = 0
    for cell in sorted(set(base) & set(cand)):
        if only and cell not in only:
            continue
        metrics = [m for m in TRAFFIC_METRICS
                   if m in base[cell] and m in cand[cell]]
        if not metrics:
            continue
        covered += 1
        for m in metrics:
            b, c = float(base[cell][m]), float(cand[cell][m])
            rel = (c - b) / b if b else 0.0
            print(f"  {cell}.{m}: {b:g} -> {c:g} ({rel:+.1%})")
        details = {m: cand[cell][m] for m in DETAIL_METRICS
                   if m in cand[cell]}
        if details:
            print(f"    detail: {details}")
    if covered == 0:
        print("check_traffic_budget: no cells with traffic counters in "
              "both files — nothing to check")
        return 0

    mix = decision_mix_violations(
        {c: m for c, m in cand.items() if not only or c in only})
    if mix:
        print("WIRE-COMPRESSION DECISION MIX FAILURE:")
        for cell, quant, total in mix:
            print(f"  {cell}: wire_quant={quant} with {total:g} window "
                  "decisions but zero sparse_q/bitmap picks")
        return 1

    pmix = pull_mix_violations(
        {c: m for c, m in cand.items() if not only or c in only})
    if pmix:
        print("PULL DECISION MIX FAILURE:")
        for cell, knob, why in pmix:
            print(f"  {cell}: {knob} armed but dead — {why}")
        return 1

    coll = collective_mix_violations(
        {c: m for c, m in cand.items() if not only or c in only})
    if coll:
        print("COLLECTIVE DECISION MIX FAILURE:")
        for cell, mode, total in coll:
            print(f"  {cell}: collective={mode} with {total:g} collective "
                  "decisions but zero sparse_allreduce picks")
        return 1

    deaths = fleet_violations(
        {c: m for c, m in cand.items() if not only or c in only})
    if deaths:
        print("FLEET UNNOTICED-DEATH FAILURE:")
        for cell, n in deaths:
            print(f"  {cell}: {n} member(s) went silent past the dead "
                  "threshold with NO supervisor exit event")
        return 1

    broken = numerics_violations(
        {c: m for c, m in cand.items() if not only or c in only})
    if broken:
        print("NUMERICS HEALTH FAILURE:")
        for cell, nonfin, crit in broken:
            print(f"  {cell}: {nonfin} nonfinite value(s), {crit} "
                  "critical anomaly event(s) — run is numerically "
                  "broken")
        return 1

    storms = retrace_violations(
        {c: m for c, m in base.items() if not only or c in only},
        {c: m for c, m in cand.items() if not only or c in only})
    if storms:
        print("RETRACE BUDGET EXCEEDED:")
        for cell, b, c in storms:
            print(f"  {cell}: {c:g} retrace(s) vs baseline {b:g} "
                  "(floor 1) — a compiled step is re-tracing; look for "
                  "shape/dtype churn in telemetry_report --compile")
        return 1

    regressions = compare(base, cand, args.tolerance, only)
    if regressions:
        print(f"TRAFFIC BUDGET EXCEEDED (tolerance {args.tolerance:.0%}):")
        for cell, metric, b, c, rel in regressions:
            print(f"  {cell}.{metric}: {b:g} -> {c:g} ({rel:+.1%})")
        return 1

    overhead = trace_overhead_report(
        {c: m for c, m in base.items() if not only or c in only},
        {c: m for c, m in cand.items() if not only or c in only},
        args.trace_overhead_bound)
    for cell, b_ms, c_ms, rel, over in overhead:
        verdict = ("OVER BOUND (advisory)" if over
                   else f"within {args.trace_overhead_bound:.0%}")
        print(f"  trace overhead {cell}: step_ms {b_ms:.3f} -> "
              f"{c_ms:.3f} ({rel:+.1%}) — {verdict}")

    for cell, b_q, c_q, rel, over in serve_qps_report(
            {c: m for c, m in base.items() if not only or c in only},
            {c: m for c, m in cand.items() if not only or c in only},
            args.tolerance):
        verdict = ("DROPPED PAST TOLERANCE (advisory)" if over
                   else f"within {args.tolerance:.0%}")
        print(f"  serve qps {cell}: {b_q:.0f} -> {c_q:.0f} "
              f"({rel:+.1%}) — {verdict}")

    print(f"traffic budget OK: {covered} cell(s) within "
          f"{args.tolerance:.0%}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
