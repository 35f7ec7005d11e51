"""Idle share of the traced window, in percent: 1 - busy / window on each
device, the largest reported (``{"kind": "trace_idle"}``)."""

from . import traced
from ..lib import xplane


def read(params: dict, ctx: dict):
    found = traced(ctx)
    if found is None:
        return None
    trace, (lo, hi) = found
    return max(100.0 * (1.0 - xplane.busy_seconds(d, (lo, hi))
                        / ((hi - lo) / 1e9)) for d in trace.devices)
