"""Readers: one small module per *kind* of per-layer metric, each with
``read(params, ctx) -> float | None``.  A reader that finds nothing to read
returns ``None`` and the harness leaves the metric out of the line."""


def traced(ctx: dict):
    """(trace, window) when the run has a device trace to read, else None."""
    trace, window = ctx.get("trace"), ctx.get("window")
    if trace is None or not trace.devices or window is None:
        return None
    return trace, window
