#!/usr/bin/env bash
# Tier-1 gate — the ROADMAP.md "Tier-1 verify" command, verbatim.
# Prints DOTS_PASSED=<n> (count of passing-test dots in the progress
# lines) and exits with pytest's return code.
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
# HARD GATE: smtpu-lint — new findings (not suppressed, not baselined)
# fail tier-1 outright.  The JSON report lands in runs/ next to the
# telemetry evidence.  See docs/OPERATIONS.md "The invariant linter".
REPO_DIR="$(dirname "$0")/.."
mkdir -p "$REPO_DIR/runs"
LINT_OUT="$REPO_DIR/runs/lint_$(date +%Y%m%d_%H%M%S).json"
echo "--- smtpu-lint (hard gate) ---"
if timeout -k 5 120 env JAX_PLATFORMS=cpu python -m swiftmpi_tpu.analysis.lint --out "$LINT_OUT"; then
  echo "smtpu-lint: clean (report: $LINT_OUT)"
else
  echo "smtpu-lint: NEW FINDINGS (report: $LINT_OUT) — tier-1 FAILS"
  if [ "$rc" -eq 0 ]; then rc=1; fi
fi
# Advisory traffic-budget check: when both env vars name readable bench
# JSONs, report wire_bytes/dispatches regressions — and input-pipeline
# stall_ms_per_step regressions past the absolute noise floor — next to
# the verdict without changing the tier-1 exit code.
if [ -n "$BENCH_BASELINE" ] && [ -n "$BENCH_CANDIDATE" ] && [ -r "$BENCH_BASELINE" ] && [ -r "$BENCH_CANDIDATE" ]; then
  echo "--- traffic budget (advisory) ---"
  python "$(dirname "$0")/check_traffic_budget.py" "$BENCH_BASELINE" "$BENCH_CANDIDATE" || echo "traffic budget ADVISORY FAILURE (tier-1 verdict unchanged)"
  # Serving-plane gate over the same files: p99 query latency +
  # hit-ratio regression on the serve_qps cell (0.1ms / 1pt noise
  # floors — check_traffic_budget.ABS_NOISE_FLOOR).  Only runs when
  # both sides actually carry the cell, so bench files from before the
  # serving plane never turn the advisory line into exit-2 noise.
  if grep -q '"serve_qps"' "$BENCH_BASELINE" && grep -q '"serve_qps"' "$BENCH_CANDIDATE"; then
    echo "--- serve budget (advisory) ---"
    python "$(dirname "$0")/check_traffic_budget.py" --cells serve_qps "$BENCH_BASELINE" "$BENCH_CANDIDATE" || echo "serve budget ADVISORY FAILURE (tier-1 verdict unchanged)"
  fi
  # Wire-compression gate: the qwire cell must hold its wire_bytes
  # budget AND its decision mix must actually pick an encoded format
  # (check_traffic_budget fails the run when wire_quant is armed but
  # the sparse_q/bitmap share is zero).  Grep-gated so bench files
  # predating the 4-way wire stay advisory-quiet.
  # Serve-fleet gate: delta_bytes_per_publish (exact byte model, hard
  # lower-is-better) + worst per-replica serve_p99_ms (0.1ms floor),
  # with the aggregate-qps drop reported advisorily.  Grep-gated so
  # bench files predating the shipping plane stay quiet.
  if grep -q '"serve_fleet"' "$BENCH_BASELINE" && grep -q '"serve_fleet"' "$BENCH_CANDIDATE"; then
    echo "--- serve-fleet budget (advisory) ---"
    python "$(dirname "$0")/check_traffic_budget.py" --cells serve_fleet "$BENCH_BASELINE" "$BENCH_CANDIDATE" || echo "serve-fleet budget ADVISORY FAILURE (tier-1 verdict unchanged)"
  fi
  if grep -q '"w2v_1m_qwire"' "$BENCH_BASELINE" && grep -q '"w2v_1m_qwire"' "$BENCH_CANDIDATE"; then
    echo "--- qwire budget (advisory) ---"
    python "$(dirname "$0")/check_traffic_budget.py" --cells w2v_1m_qwire "$BENCH_BASELINE" "$BENCH_CANDIDATE" || echo "qwire budget ADVISORY FAILURE (tier-1 verdict unchanged)"
  fi
fi
# Advisory TSan lane: when the toolchain can build AND run
# -fsanitize=thread, hammer SmtpuPrefetcher's producer/consumer queue
# (native/tsan_prefetcher.cpp).  A detected race prints loudly but
# does not fail tier-1 — TSan availability varies by container; the
# capability-probed pytest twin is tests/test_native_tsan.py.
if printf 'int main(){return 0;}' | ${CXX:-g++} -fsanitize=thread -x c++ - -o /tmp/_tsan_probe 2>/dev/null && /tmp/_tsan_probe 2>/dev/null; then
  echo "--- tsan lane (advisory) ---"
  if make -C "$REPO_DIR/native" tsan >/dev/null 2>&1 && TSAN_OPTIONS="halt_on_error=0 exitcode=66" timeout -k 5 300 "$REPO_DIR/native/tsan_prefetcher"; then
    echo "tsan lane: clean"
  else
    echo "tsan lane ADVISORY FAILURE (tier-1 verdict unchanged)"
  fi
fi
rm -f /tmp/_tsan_probe
# Advisory 4-process fleet observability smoke (ISSUE 12): launches 4
# _fleet_child ranks with an injected stall, merges them with a
# FleetCollector, and checks straggler attribution + member health.
# Capability-probed inside fleet_smoke.py (prints FLEET_SMOKE SKIP with
# the reason and exits 0 where subprocess spawning is unavailable).
# Artifacts (per-rank streams + supervisor.jsonl + merged fleet.jsonl)
# land under runs/ next to the lint report, followed by an advisory
# `telemetry_report.py --fleet` read of the merged timeline.
FLEET_OUT="$REPO_DIR/runs/fleet_$(date +%Y%m%d_%H%M%S)"
echo "--- fleet smoke (advisory) ---"
if timeout -k 10 300 env JAX_PLATFORMS=cpu python "$(dirname "$0")/fleet_smoke.py" --out "$FLEET_OUT"; then
  if [ -r "$FLEET_OUT/fleet.jsonl" ]; then
    python "$(dirname "$0")/telemetry_report.py" --fleet "$FLEET_OUT/fleet.jsonl" || echo "fleet report ADVISORY FAILURE (tier-1 verdict unchanged)"
  fi
else
  echo "fleet smoke ADVISORY FAILURE (tier-1 verdict unchanged)"
fi
# Advisory numerics-health smoke (ISSUE 13): the same 4-process fleet
# drill with the numerics plane armed and a 40x grad-norm spike
# injected on rank 0 at step 30 — the merged timeline must carry the
# anomaly (fleet_smoke.py fails otherwise), and the rendered
# `telemetry_report.py --numerics` read of rank 0's stream shows the
# series stats + anomaly timeline an operator would triage from
# (docs/OPERATIONS.md "Numerics anomaly triage").
NUM_OUT="$REPO_DIR/runs/numerics_$(date +%Y%m%d_%H%M%S)"
echo "--- numerics smoke (advisory) ---"
if timeout -k 10 300 env JAX_PLATFORMS=cpu python "$(dirname "$0")/fleet_smoke.py" --out "$NUM_OUT" --numerics-spike 30; then
  NUM_STREAM=$(ls "$NUM_OUT"/telemetry_*.jsonl 2>/dev/null | head -1)
  if [ -n "$NUM_STREAM" ]; then
    python "$(dirname "$0")/telemetry_report.py" --numerics "$NUM_STREAM" || echo "numerics report ADVISORY FAILURE (tier-1 verdict unchanged)"
  fi
  if [ -r "$NUM_OUT/fleet.jsonl" ]; then
    python "$(dirname "$0")/telemetry_report.py" --fleet "$NUM_OUT/fleet.jsonl" || echo "numerics fleet report ADVISORY FAILURE (tier-1 verdict unchanged)"
  fi
else
  echo "numerics smoke ADVISORY FAILURE (tier-1 verdict unchanged)"
fi
# Advisory wire-trace smoke (ISSUE 15): the same 4-process fleet drill
# with the flight recorder armed — every child emits synthetic windows,
# rank 0 drops a trace_trigger.json mid-run, and fleet_smoke.py checks
# that every rank left a parseable trigger dump and that the merged
# timeline correlates same-id windows across ranks.  A rendered
# `telemetry_report.py --trace` read of rank 0's dump shows the
# per-window "why" an operator would triage from (docs/OPERATIONS.md
# "Explaining a window's wire decision").
TRACE_OUT="$REPO_DIR/runs/trace_smoke_$(date +%Y%m%d_%H%M%S)"
echo "--- trace smoke (advisory) ---"
if timeout -k 10 300 env JAX_PLATFORMS=cpu python "$(dirname "$0")/fleet_smoke.py" --out "$TRACE_OUT" --trace; then
  TRACE_DUMP=$(ls "$TRACE_OUT"/trace_r0_p*.jsonl 2>/dev/null | head -1)
  if [ -n "$TRACE_DUMP" ]; then
    python "$(dirname "$0")/telemetry_report.py" --trace "$TRACE_DUMP" || echo "trace report ADVISORY FAILURE (tier-1 verdict unchanged)"
  fi
else
  echo "trace smoke ADVISORY FAILURE (tier-1 verdict unchanged)"
fi
# Advisory elastic chaos drill (ISSUE 16): a 4-process elastic world
# under launch.py -elastic 1 — rank 2 is SIGKILLed mid-run, survivors
# repartition its rows at the next safe point (epoch 1, death), the
# supervisor restarts it and re-admits it through the two-phase rejoin
# (epoch 2, commit).  fleet_smoke.py --elastic checks the kill was
# attributed (organic exit, never unnoticed), the epoch advanced, a
# commit landed, migration bytes were booked, and every rank ended the
# drill on the final epoch (fleet_reconverge_steps is finite).
EL_OUT="$REPO_DIR/runs/elastic_smoke_$(date +%Y%m%d_%H%M%S)"
echo "--- elastic smoke (advisory) ---"
if timeout -k 10 300 env JAX_PLATFORMS=cpu python "$(dirname "$0")/fleet_smoke.py" --out "$EL_OUT" --elastic; then
  if [ -r "$EL_OUT/fleet.jsonl" ]; then
    python "$(dirname "$0")/telemetry_report.py" --fleet "$EL_OUT/fleet.jsonl" || echo "elastic fleet report ADVISORY FAILURE (tier-1 verdict unchanged)"
  fi
else
  echo "elastic smoke ADVISORY FAILURE (tier-1 verdict unchanged)"
fi
# Advisory serve-fleet chaos drill (ISSUE 17): a trainer + 3 replica
# world under launch.py -serve 3 — the trainer ships versioned snapshot
# deltas through transfer/delta.py, replicas replay them and run paced
# query storms, and one replica is SIGKILLed mid-storm.  fleet_smoke.py
# --serve checks the kill was attributed (never unnoticed), survivors
# kept serving, the restarted replica re-synced to the manifest tail
# via base+delta replay, and every replica's version stream stayed
# monotone per life.
SERVE_OUT="$REPO_DIR/runs/serve_smoke_$(date +%Y%m%d_%H%M%S)"
echo "--- serve smoke (advisory) ---"
if timeout -k 10 300 env JAX_PLATFORMS=cpu python "$(dirname "$0")/fleet_smoke.py" --out "$SERVE_OUT" --serve; then
  if [ -r "$SERVE_OUT/fleet.jsonl" ]; then
    python "$(dirname "$0")/telemetry_report.py" --fleet "$SERVE_OUT/fleet.jsonl" || echo "serve fleet report ADVISORY FAILURE (tier-1 verdict unchanged)"
  fi
else
  echo "serve smoke ADVISORY FAILURE (tier-1 verdict unchanged)"
fi
exit $rc
