#!/usr/bin/env python3
"""Read a cell's per-layer metrics off a kept trace again.

    python3 benchmark/run.py --workload <cell> ... --trace 1 --keep-trace DIR
    python3 benchmark/tools/read_kept_trace.py DIR [--tree CHECKOUT]

``DIR`` holds the run's ``.xplane.pb`` and ``context.json`` (the counters,
the harness's clock readings, the memory peaks, the cost floor and the
programs' phase maps the readers had; ``ctx["phase_maps"]`` gives them to
``trace_scope``).  ``--tree`` reads with another checkout's ``BENCHMARK.json``,
metric files and readers (any tree from PR 51 on) to show that an entry
renamed, folded or re-listed, or a reader changed, reads what the old one
read from the same trace.  No chip: nothing here touches a device.
Prints one JSON object, ``{metric: value}``, as its last line.
"""

import argparse
import glob
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("dir")
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.tree))
    from benchmark import run as harness
    from benchmark.lib import spec, xplane

    with open(os.path.join(args.dir, "context.json")) as f:
        kept = json.load(f)
    found = glob.glob(os.path.join(args.dir, "*.xplane.pb"))
    if len(found) != 1:
        raise SystemExit(f"expected one .xplane.pb in {args.dir}, found "
                         f"{len(found)}")
    trace = xplane.load(found[0])
    cell = spec.load_cell(kept.pop("cell"))
    ctx = dict(kept, trace=trace, window=trace.window(harness.ANCHOR))
    print(json.dumps({name: m["value"] for name, m in
                      harness.layer_metrics(cell, ctx).items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
