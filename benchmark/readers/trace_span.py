"""Host time inside the program's spans, or the device idle time credited to
them, a step.

``{"kind": "trace_span", "span": "<regex>", "report": "host_ms_per_step"}``
is the time, in milliseconds a step, the harness's thread (the one that
carries ``bench/window``) spent inside the ``obs.span``s whose name matches
``span``, within the traced window (their union, should two nest).
``"report": "idle_ms_per_step"`` is device 0's idle time credited to them
instead: ``xplane.idle_gaps_by_span`` with the harness's anchor over
``SPANS`` below, so each idle gap goes to the innermost of those spans open at
the time, and the matching names are summed.

The contract with the program: ``obs.span(name)`` is a
``jax.profiler.TraceAnnotation`` of that name when ``[worker] telemetry`` is
on, i.e. on the profiler's clock.  ``Word2Vec.train`` emits ``train_setup``
(entry to the first ``next(items)``), ``input_wait``, ``h2d``, ``dispatch``
(one per item of the loop), ``loss_fetch`` (the epoch's blocking fetch) and
``train_finish`` (from the fetch to the return).

``None`` when there is no device plane or no event of the window matches
``span`` (a commit that predates the span).
"""

import re

from . import traced
from ..lib import xplane

# The harness's annotations, the loop's spans and the CALL-LEVEL spans, on
# purpose not ``obs.catalog.HOST_SPANS``: a span the program opens inside a
# call-level one (``loss_wait`` in ``loss_fetch``) leaves its idle time with
# its parent here, which is what ``device.idle_in_call_overhead_ms_per_step``
# reads and ``tests/test_trace_host.py`` (tier-1) holds; ``trace_host`` reads
# by the program's own list.
ANCHOR = r"^bench/window$"
SPANS = (r"^(bench/|render$|h2d$|input_wait$|dispatch$|train_setup$|"
         r"loss_fetch$|train_finish$)")


def read(params: dict, ctx: dict):
    found = traced(ctx)
    if found is None:
        return None
    trace, (lo, hi) = found
    anchor, rx = re.compile(ANCHOR), re.compile(params["span"])
    line = next((ln for ln in trace.host_lines.values()
                 if any(anchor.search(e[2]) for e in ln)), [])
    hit = xplane.clip([e for e in line if rx.search(e[2])], lo, hi)
    if not hit:
        return None
    if params["report"] == "idle_ms_per_step":
        idle = xplane.idle_gaps_by_span(trace, trace.devices[0], (lo, hi),
                                        ANCHOR, SPANS)
        return 1e3 * sum(s for name, s in idle.items()
                         if rx.search(name)) / ctx["steps"]
    return xplane.total(hit) / 1e6 / ctx["steps"]
