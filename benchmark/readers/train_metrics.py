"""A counter the program returns per ``train()`` call, the median over the
traced chunks: ``{"kind": "train_metrics", "key": "stall_ms_per_step"}``."""

import statistics


def read(params: dict, ctx: dict):
    values = [c[params["key"]] for c in ctx["counters"]
              if params["key"] in c]
    return float(statistics.median(values)) if values else None
