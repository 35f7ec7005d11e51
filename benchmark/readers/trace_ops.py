"""Device time of the ops whose name matches a regex, from the trace.

``{"kind": "trace_ops", "ops": "<regex>", "report": "ms_per_step"}`` is the
union of their intervals a step, in milliseconds, mean over the device planes
whose op names can be read (``xplane.named_devices``: the same planes as
``trace_scope`` reads, so a kernel's time stands beside its scope's);
``"report": "exposed_share"`` is the part of that time in which no other op
ran on the same device, as a percentage of the traced window.  A regex that
matches no op reads 0: the ops are absent, the trace is not."""

import statistics

from . import traced
from ..lib import xplane


def read(params: dict, ctx: dict):
    found = traced(ctx)
    if found is None:
        return None
    trace, (lo, hi) = found
    pairs = [xplane.matching_seconds(d, (lo, hi), params["ops"])
             for d in xplane.named_devices(trace, (lo, hi))[0]]
    if params["report"] == "exposed_share":
        return 100.0 * statistics.fmean(e for _, e in pairs) \
            / ((hi - lo) / 1e9)
    return 1e3 * statistics.fmean(t for t, _ in pairs) / ctx["steps"]
