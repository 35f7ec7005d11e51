#!/usr/bin/env python3
"""One command, one process, one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The process holds the chip(s) itself and starts nothing that imports JAX.
The last line of standard output is one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` in a traced run), then ``compared``: each number ``correct``
compared beside its limit; everything else goes on earlier lines.
Without a TPU, or with another device count than the cell's ``chips``, it
exits non-zero and prints no result line; ``--rehearse-cpu`` runs the cell
at its toy size on the CPU backend and prints ``platform: cpu`` and no time,
rate or device metric.  See ``benchmark/README.md``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()     # set-up is counted from process start

import argparse                   # noqa: E402
import importlib                  # noqa: E402
import json                       # noqa: E402
import math                       # noqa: E402
import os                         # noqa: E402
import sys                        # noqa: E402
import tempfile                   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import device as devlib   # noqa: E402
from benchmark.lib import loop, spec, xplane  # noqa: E402

ANCHOR = r"^bench/window$"


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="measured window (default: run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy size on the CPU backend: counts and "
                         "correctness, no time or device metric")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's .xplane.pb into DIR, with "
                         "what tools/read_kept_trace.py needs beside it")
    return ap.parse_args(argv)


def prepare_backend(chips: int, rehearse_cpu: bool) -> None:
    """Environment the first JAX import reads.  The plain reference runs on
    the host CPU backend, so a platform list that names the TPU alone gets
    the CPU appended; a rehearsal gets ``chips`` virtual devices unless the
    caller's XLA_FLAGS already chose a count."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if rehearse_cpu and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={chips}")


def layer_metrics(cell, ctx: dict) -> dict:
    out = {}
    for entry, meta in cell.per_layer:
        reader = importlib.import_module(
            f"benchmark.readers.{meta['reader']['kind']}")
        value = reader.read(meta["reader"], ctx)
        if value is not None and math.isfinite(value):
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def keep_context(dirname: str, cell, ctx: dict) -> None:
    """What the readers had beside the kept ``.xplane.pb``: with it
    ``tools/read_kept_trace.py`` reads the cell's per-layer metrics off the
    same trace again, with this tree's readers or another's."""
    from benchmark.readers import trace_scope

    programs = {meta["reader"]["program"] for _e, meta in cell.per_layer
                if "program" in meta["reader"]}
    kept = {k: ctx[k] for k in ("harness", "counters", "steps", "memory",
                                "floor")}
    kept.update(cell=cell.name, phase_maps={
        p: trace_scope.program_phase_map(p) for p in sorted(programs)})
    with open(os.path.join(dirname, "context.json"), "w") as f:
        json.dump(kept, f, default=float)


def compared_numbers(cell, first, rows, warm, win, loss_fixed, band, places,
                     peaks) -> dict:
    """``{name: [number, limit]}``: every number ``correct`` compares, each
    beside its limit.  ``checks`` is computed from this dict (``within``), so
    what a run prints is what decided it.  A limit is an upper one, or a
    ``[low, high]`` band; a condition is the count of its breaches beside 0.
    ``train_loss_fixed`` has a band on the chip only (none is compared in a
    CPU rehearsal, and no entry is made)."""
    rtol = getattr(importlib.import_module(
        f"benchmark.reference.{cell.family}"), "RTOL", None)
    out = {f"first_step.{k}": [float(v["max_err"]), v.get("limit", rtol)]
           for k, v in first["fields"].items()}
    # the family's own verdict: the fields above, and what it holds beside
    # them without a number (an update that is not zero, rows kept)
    out["first_step.not_ok"] = [int(not first["ok"]), 0]
    for k, v in rows.items():
        if isinstance(v, str):               # "moved/sampled"
            done, of = (int(x) for x in v.split("/"))
            out[f"rows.not_{k}"] = [of - done, 0]
        else:                                # a condition, "ok" with them
            out[f"rows.not_{k}"] = [int(not v), 0]
    losses = [first["loss"], loss_fixed] + [c.loss for c in warm + win.chunks]
    out["losses.not_finite"] = [sum(not math.isfinite(x) for x in losses), 0]
    if band is not None:
        out["train_loss_fixed"] = [loss_fixed, list(band)]
    out["window.programs_lowered"] = [win.compiles["lowered"], 0]
    out["table.fields_misplaced"] = [sum(len(p["why"]) for p in places), 0]
    if peaks is not None:
        out["table.bytes_over_peaks"] = [
            max(0, places[0]["table_bytes"] - sum(peaks)), 0]
    out["steps.failed"] = [win.failed + sum(c.failed for c in warm), 0]
    return out


def within(compared: dict, prefix: str) -> bool:
    """Every compared number under ``prefix`` is finite and in its limit."""
    ok = True
    for name, (value, limit) in compared.items():
        if name.startswith(prefix):
            lo, hi = limit if isinstance(limit, list) else (-math.inf, limit)
            ok = ok and math.isfinite(value) and lo <= value <= hi
    return ok


def reduce_trace(path: str, steps: int):
    """(Trace, window, device block, breakdown) of a traced window."""
    from benchmark.readers import trace_host

    trace = xplane.load(path)
    window = trace.window(ANCHOR)
    if not trace.devices or window is None:
        return trace, window, {}, None
    lo, hi = window
    busy = [xplane.busy_seconds(d, window) for d in trace.devices]
    ops = {}
    named, left_out = xplane.named_devices(trace, window)
    for d in named:
        for name, s in xplane.op_seconds(d, window,
                                         xplane.op_group).items():
            ops[name] = ops.get(name, 0.0) + s / len(named)
    # host spans an idle gap can be credited to: the harness's own and the
    # program's (obs.span names, TraceAnnotations when [worker] telemetry: 1)
    spans = trace_host.span_pattern(trace_host.program_spans(),
                                    also="bench/.*|")
    idle = xplane.idle_gaps_by_span(trace, trace.devices[0], window,
                                    ANCHOR, spans)
    block = {"busy_s": sum(busy) / len(busy), "window_s": (hi - lo) / 1e9}
    breakdown = {"device_ops": xplane.top(ops), "idle_gaps": xplane.top(idle)}
    log(f"trace: {len(trace.devices)} device plane(s), window "
        f"{block['window_s']:.3f}s, busy " + " ".join(f"{b:.3f}s"
                                                      for b in busy)
        + f", {steps} steps" + xplane.left_out_text(left_out))
    return trace, window, block, breakdown


def main(argv=None) -> int:
    args = parse_args(argv)
    problems = spec.check()
    if problems:
        raise SystemExit("[bench] BENCHMARK.json does not resolve:\n  "
                         + "\n  ".join(problems))
    cell = spec.load_cell(args.workload, rehearse=args.rehearse_cpu)
    bench = spec.load_benchmark()
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])
    prepare_backend(cell.chips, args.rehearse_cpu)

    import jax

    devices = devlib.require_devices(cell.chips, args.rehearse_cpu)
    dev = devices[0]
    # every program of a run, however small, comes from the persistent
    # cache the second time: set-up is paid by every run of every check
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = loop.CompileCounter()
    annotate = jax.profiler.TraceAnnotation
    log(f"cell {cell.name}: config {cell.config['name']}, traffic "
        f"{cell.traffic['name']}, seed {args.seed}, trace {args.trace}; "
        f"platform: {dev.platform} kind: {dev.device_kind} count: "
        f"{len(devices)}" + (" (CPU rehearsal at toy size)"
                             if args.rehearse_cpu else ""))

    fam_mod = importlib.import_module(f"benchmark.families.{cell.family}")
    with tempfile.TemporaryDirectory(prefix="smtpu_bench_") as workdir:
        family = fam_mod.Family(cell.config, cell.traffic, args.seed,
                                workdir, telemetry=bool(args.trace),
                                annotate=annotate)
        try:
            return run_cell(args, cell, family, counter, devices, seconds,
                            workdir, annotate)
        finally:
            family.close()


def run_cell(args, cell, family, counter, devices, seconds, workdir,
             annotate) -> int:
    dev = devices[0]
    device_run = dev.platform == "tpu"
    steps = int(cell.traffic["chunk_steps"])
    costs = importlib.import_module(f"benchmark.costs.{cell.family}")

    t0 = time.perf_counter()
    harness = {"start_s": t0 - T_START}      # imports, backend bring-up
    family.make_inputs()
    t1 = time.perf_counter()
    harness["data_s"] = t1 - t0
    family.build_model()
    t2 = time.perf_counter()
    harness["model_build_s"] = t2 - t1
    place = family.placement(dev.platform)
    log(f"table: {place['table_bytes'] / 1e9:.3f} GB over {place['shards']} "
        f"shard(s), {place['table_bytes_per_device'] / 1e9:.3f} GB a chip; "
        f"compile cache {family.cache_dir}")

    first = family.first_step_check()
    t3 = time.perf_counter()
    harness["reference_check_s"] = t3 - t2 - first["train_call_s"]
    log("first step vs plain reference: " + ("ok" if first["ok"] else "FAIL")
        + f" over {first['rows_checked']} rows; max err "
        + " ".join(f"{f}={v['max_err']:.2e}"
                   for f, v in first["fields"].items())
        + f"; sampler |p - unigram^0.75| <= "
        f"{first['sampler_max_abs_err']:.1e}")

    warm = [loop.run_chunk(family, steps)
            for _ in range(int(cell.traffic["warmup_chunks"]))]
    t4 = time.perf_counter()
    harness["warm_s"] = first["train_call_s"] + t4 - t3
    loss_fixed, ns_loss = family.eval_loss()
    t5 = time.perf_counter()
    harness["eval_s"] = t5 - t4
    setup_s = t5 - T_START
    setup_compiles = counter.mark()
    log("set-up parts (s): " + " ".join(
        f"{k[:-2]}={v:.2f}" for k, v in harness.items())
        + f" total={setup_s:.2f}; programs lowered {setup_compiles['lowered']}"
        f", of them compiled by XLA {setup_compiles['compiled']}, read from "
        f"the persistent cache {setup_compiles['cache_hits']}")

    trace = window = breakdown = None
    block = {}
    if args.trace:
        win, path = loop.traced(family, steps,
                                int(cell.traffic["trace_chunks"]), counter,
                                os.path.join(workdir, "profile"), annotate)
        trace, window, block, breakdown = reduce_trace(path, win.attempted)
        if args.keep_trace:
            import shutil
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(path, args.keep_trace)
    else:
        win = loop.measure(family, steps, counter,
                           lambda w: w.seconds >= seconds)
    rate = win.words_per_s()
    log(f"window: {len(win.chunks)} chunks of {steps} steps in "
        f"{win.seconds:.2f}s, {win.failed} of {win.attempted} steps failed, "
        f"programs lowered inside: {win.compiles['lowered']}"
        + (f"; words/s by chunk (median {rate:.1f}"
           f"{', profiler on' if args.trace else ''}): "
           + " ".join(f"{c.words / c.seconds:.0f}" for c in win.chunks)
           if device_run and rate else ""))

    rows = family.rows_check()
    place_end = family.placement(dev.platform)
    peaks = devlib.peak_bytes(devices)
    log(f"memory_stats of device 0: {devices[0].memory_stats()}")
    band = cell.band.get("train_loss_fixed") if device_run else None
    compared = compared_numbers(cell, first, rows, warm, win, loss_fixed,
                                band, (place, place_end), peaks)
    checks = {
        "first_step_matches_reference": within(compared, "first_step."),
        "rows": within(compared, "rows."),
        "losses_finite": within(compared, "losses."),
        "loss_in_band": within(compared, "train_loss_fixed"),
        "no_compilation_in_window": within(compared, "window."),
        "table_resident": within(compared, "table."),
        "no_failed_step": within(compared, "steps."),
    }
    if device_run and not cell.band:
        log("no benchmark/bands file for this cell: loss band not checked")
    log("checks: " + " ".join(f"{k}={v}" for k, v in checks.items())
        + f"; rows {rows}; train_loss_fixed {loss_fixed:.6g} (NS objective "
        f"of the same batch {ns_loss:.6g})"
        + (f" band {band}" if band else ""))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(peaks) if peaks else None, **block}
    if args.trace:
        ctx = {"harness": harness,
               "counters": family.counters[-len(win.chunks):],
               "trace": trace, "window": window, "steps": win.attempted,
               "memory": {"peak_bytes": peaks, "table_bytes_per_device":
                          place["table_bytes_per_device"]},
               "floor": costs.step_floor_seconds(
                   family.step_shape(len(devices)),
                   devlib.peaks_for(dev.device_kind))
               if device_run else None}
        metrics = layer_metrics(cell, ctx)
        if args.keep_trace:
            keep_context(args.keep_trace, cell, ctx)
        if not device_run:
            log(f"rehearsal readings, not metrics: {metrics}")
            metrics = {}
    else:
        values = {"train_loss_fixed": loss_fixed}
        if device_run:
            values.update(words_per_s=rate, setup_s=setup_s,
                          peak_hbm_gb=max(peaks) / 1e9)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if values.get(m["name"])
                   is not None}
    result = {"correct": all(checks.values()), "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # the contract: each number compared beside its limit, as the result's
    # last key and the run's last lines on standard error
    result["compared"] = compared
    for name, (value, limit) in compared.items():
        print(f"[bench] compared {name}: {value!r} limit {limit!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
