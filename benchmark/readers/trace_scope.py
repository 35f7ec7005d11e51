"""Device time of one phase of a tracked program, from the trace joined with
the program's own phase map.

``{"kind": "trace_scope", "program": "w2v_step", "phase": "<name>"}`` reads
milliseconds a step, mean over the devices, of the self-time segments
(``xplane.device_segments``: the innermost op owns the instant) that

* fall inside an execution of the program's HLO module on that device —
  ``Device.modules`` gives the intervals, which keeps ``fusion.16`` of
  ``jit__threefry_split`` apart from ``fusion.16`` of ``jit_step`` — and
* belong, by instruction name (the first word of the op label:
  ``fusion.24``, ``copy.141.remat3``), to ``phase`` in
  ``swiftmpi_tpu.obs.costs.phase_map(program)``.

The contract with the program: ``phase_map(name)`` returns ``{"module": the
module's name as the trace prints it before ``(<id>)``, "phase": {instruction
name: phase}, ...}`` or ``None``; the phase of an instruction is the innermost
``obs.named_scope`` of ``obs.catalog.DEVICE_SCOPES`` in its ``op_name``, a
fusion takes the phase most of its fused instructions carry, and what is under
no scope is ``"unscoped"`` — the program guesses no phase from a shape, and
neither does this reader.  ``"phase": "unscoped"`` reads that remainder (an
executed instruction the map does not know counts there too), so the phases
of one program sum to the device time spent inside it.

A device plane whose ops the profiler labelled by something else than the
instruction's name (``region.<n>``: one plane of a four-chip trace, a
different one from run to run) tells nothing about phases: all of it would
count as ``unscoped`` and the mean over the planes would take that much from
every scope.  ``xplane.named_devices`` decides once, for every reader that
goes by an op's name, which planes are read; the planes it leaves out are
printed here.  A name the map does not know on a plane that is read (a stale
map) counts as ``unscoped``, as it always did; the line says how much.

``None`` (the metric is left out of the line) when there is no device plane,
the program has no ``phase_map`` (a commit that predates it) or no map (no
handle ran with telemetry on; an executable served from a compile cache
written before the scopes existed carries no phase: clear the cache once), or
the module did not run inside the window.  The map is computed after the
window: it lowers and compiles from remembered shapes, a compile-cache read.
"""

import bisect
import re
import statistics

from . import traced
from ..lib import xplane

UNSCOPED = "unscoped"
_RUN = re.compile(r"^(.*?)(\(\d+\))?$")     # "jit_step(7043...)" -> jit_step


def program_phase_map(program: str):
    try:
        from swiftmpi_tpu.obs import costs
        return costs.phase_map(program)
    except (ImportError, AttributeError):
        return None


def phase_ms_per_step(trace, window, steps: int, pm: dict):
    """``({phase: ms a step, mean over the planes read}, unknown)``, or
    ``None`` when ``pm["module"]`` never ran in the window.  ``unknown`` has
    the op segments the map does not know and their time (``"segments"``,
    ``"ms_per_step"``, on the planes read) and the planes
    ``xplane.named_devices`` left out (``"left_out"``)."""
    lo, hi = window
    devices, left_out = xplane.named_devices(trace, window)
    per_device, segments, unknown_ns = [], 0, 0.0
    for dev in devices:
        runs = sorted((s, e) for s, e, name in xplane.clip(dev.modules, lo, hi)
                      if _RUN.match(name).group(1) == pm["module"])
        if not runs:
            continue
        starts = [r[0] for r in runs]
        acc = {}
        for s, e, label in xplane.device_segments(dev, window):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= runs[i][1]:
                continue                     # another program's op
            # "%slice-start.1 = ((s32[...": a label op_label left as text
            phase = pm["phase"].get(label.split(" ", 1)[0].lstrip("%"))
            if phase is None:
                segments += 1
                unknown_ns += e - s
                phase = UNSCOPED
            acc[phase] = acc.get(phase, 0.0) + (e - s)
        per_device.append(acc)
    if not per_device:
        return None
    phases = {p for acc in per_device for p in acc}
    by_phase = {p: statistics.fmean(acc.get(p, 0.0) for acc in per_device)
                / 1e6 / steps for p in phases}
    return by_phase, {"segments": segments, "left_out": left_out,
                      "ms_per_step": unknown_ns / len(per_device) / 1e6
                      / steps}


def read(params: dict, ctx: dict):
    found = traced(ctx)
    if found is None:
        return None
    trace, window = found
    cache = ctx.setdefault("_trace_scope", {})
    program = params["program"]
    if program not in cache:
        # a kept trace brings the maps its run had (tools/read_kept_trace.py)
        maps = ctx.get("phase_maps")
        pm = program_phase_map(program) if maps is None else maps.get(program)
        cache[program] = None if pm is None else \
            phase_ms_per_step(trace, window, ctx["steps"], pm)
        if cache[program] is not None:
            by_phase, unknown = cache[program]
            print(f"[bench] trace_scope: {program} = {pm['module']}, "
                  f"{pm['instructions']} instructions, {pm['unscoped']} "
                  f"under no scope; {unknown['segments']} traced op "
                  f"segments not in the map ({unknown['ms_per_step']:.3f} ms "
                  "a step, read as unscoped); ms a step by phase: "
                  + " ".join(f"{p}={v:.3f}" for p, v in
                             sorted(by_phase.items()))
                  + xplane.left_out_text(unknown["left_out"]), flush=True)
    if cache[program] is None:
        return None
    return cache[program][0].get(params["phase"], 0.0)
