#!/usr/bin/env python
"""One-command on-chip tuning sweep for the headline w2v step.

Runs bench.py's w2v cell across a BATCH x SCAN grid in THIS process (the
chip belongs to one process) and prints a words/s table plus the best
cell as a BENCH_* env suggestion.  Exits non-zero when there is no TPU;
a cell that raises ends the sweep.

Run (on the chip): python scripts/step_sweep.py
                   SWEEP_CELLS="16384:8,32768:8" python scripts/step_sweep.py
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402

DEFAULT_CELLS = [(8192, 16), (16384, 8), (16384, 16), (24576, 8),
                 (32768, 8), (32768, 16), (49152, 4), (49152, 8),
                 (65536, 4), (65536, 8)]


def main():
    import jax

    from swiftmpi_tpu.utils.xla_env import ensure_compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"step_sweep: no TPU (jax.devices()[0] is "
                 f"{device.platform!r}) — nothing to sweep")
    ensure_compile_cache()
    cells = DEFAULT_CELLS
    if os.environ.get("SWEEP_CELLS"):
        cells = [tuple(int(x) for x in c.split(":"))
                 for c in os.environ["SWEEP_CELLS"].split(",")]
    best = None
    print(f"device: {device.device_kind}", flush=True)
    print(f"{'batch':>7} {'scan':>5} {'words/s':>12} {'step_ms':>9} "
          f"{'rendering':>10}", flush=True)
    for batch, scan in cells:
        built = bench._build_w2v(device, inner_steps=scan, batch=batch)
        w2v = bench._bench_w2v(device, bench.TIMED_CALLS["tpu"], built)
        w = w2v["words_per_sec"]
        s = w2v["step_ms"]
        # rendering per row: a throughput delta must never be silently
        # attributed to batch/scan alone
        r = w2v.get("rendering") or "?"
        print(f"{batch:7d} {scan:5d} {w:12.0f} {s:9.2f} {r:>10}",
              flush=True)
        if best is None or w > best[2]:
            best = (batch, scan, w, r)
    print(f"\nbest: BENCH_BATCH={best[0]} BENCH_SCAN={best[1]} "
          f"-> {best[2]:.0f} words/s ({best[3]})", flush=True)
    print(json.dumps({"best_batch": best[0], "best_scan": best[1],
                      "best_words_per_sec": round(best[2], 1),
                      "best_rendering": best[3]}),
          flush=True)


if __name__ == "__main__":
    main()
