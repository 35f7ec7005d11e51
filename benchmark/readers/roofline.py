"""A program's share of its roofline, in percent: the least time the chip
could take for a step — the family's ``costs`` bytes over the peak HBM rate
or its operations over the peak FLOP rate, whichever is larger — over the
device time a step took in the trace, mean over the chips
(``{"kind": "roofline"}``)."""

from . import trace_busy


def read(params: dict, ctx: dict):
    busy_ms = trace_busy.read({}, ctx)
    floor = ctx.get("floor")
    if not busy_ms or floor is None:
        return None
    return 100.0 * floor["seconds"] / (busy_ms / 1e3)
