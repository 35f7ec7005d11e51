"""Program executions a step: those that started in the traced window on
device 0, over the window's steps (``{"kind": "trace_count"}``)."""

from . import traced
from ..lib import xplane


def read(params: dict, ctx: dict):
    found = traced(ctx)
    if found is None or not found[0].devices[0].modules:
        return None
    trace, window = found
    return xplane.launches(trace.devices[0], window) / ctx["steps"]
