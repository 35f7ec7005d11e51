"""Device busy time a step, in milliseconds: the union of the intervals in
which an operation ran in the traced window, mean over the devices, over the
window's steps (``{"kind": "trace_busy"}``)."""

import statistics

from . import traced
from ..lib import xplane


def read(params: dict, ctx: dict):
    found = traced(ctx)
    if found is None:
        return None
    trace, window = found
    busy = statistics.fmean(xplane.busy_seconds(d, window)
                            for d in trace.devices)
    return 1e3 * busy / ctx["steps"]
