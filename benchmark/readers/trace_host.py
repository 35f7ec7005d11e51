"""The train loop's host time and the device's idle time by the program's
own spans, with the span list read from the program.

``trace_span`` holds a frozen list of span names; this reader takes
``swiftmpi_tpu.obs.catalog.HOST_SPANS`` instead, so a span a later PR
declares there is credited here without an edit.  Same file, same anchor
(``bench/window``) and same thread as ``trace_span``; the harness's own
``bench/*`` annotations take no credit (``bench/next_batch`` runs inside
``input_wait`` and leaves it there), ``bench/train_call`` only bounds the
stretch the program's spans have to cover.

``{"kind": "trace_host", "report": ...}``:

* ``"unspanned_ms_per_step"``: time of the anchor thread inside
  ``bench/train_call`` and inside no ``HOST_SPANS`` span, in milliseconds
  over the window's steps: the coverage guard.
* ``"idle_ms_per_step"`` with ``"span": "<regex>"``: device 0's idle time
  credited to the innermost open program span, summed over the names that
  match; with ``"span": null``: its idle time while no program span is
  open.  The names partition the idle time: every gap goes to one name or
  to none.
* ``"fixed_ms_per_call"``: the union of ``train_setup``, ``loss_fetch`` and
  ``train_finish`` less ``loss_wait`` (the wait for the queued steps, which
  is the device working), over the ``train_setup`` spans of the window:
  what one ``train()`` / ``run()`` call costs the host whatever its length.

``None`` when there is no device plane or no event of the window to read
(a commit that predates a span).
"""

import re

from . import traced
from ..lib import xplane

ANCHOR = r"^bench/window$"
CALL = r"^bench/train_call$"
FIXED = ("train_setup", "loss_fetch", "train_finish")
WAIT = "loss_wait"


def program_spans() -> tuple:
    """The program's declared span names; none where it declares none."""
    try:
        from swiftmpi_tpu.obs.catalog import HOST_SPANS
    except ImportError:
        return ()
    return tuple(HOST_SPANS)


def span_pattern(names, also: str = "") -> str:
    """A regex for exactly these span names (and ``also``, a regex)."""
    return "^(" + also + "|".join(re.escape(n) for n in names) + ")$"


def anchor_line(trace) -> list:
    """Events of the host thread that carries the harness's window."""
    rx = re.compile(ANCHOR)
    return next((ln for ln in trace.host_lines.values()
                 if any(rx.search(e[2]) for e in ln)), [])


def read(params: dict, ctx: dict):
    found = traced(ctx)
    names = program_spans()
    if found is None or not names:
        return None
    trace, (lo, hi) = found
    line = xplane.clip(anchor_line(trace), lo, hi)
    spans = [e for e in line if e[2] in names]
    if not spans:
        return None
    report = params["report"]
    if report == "unspanned_ms_per_step":
        call = re.compile(CALL)
        calls = [e for e in line if call.search(e[2])]
        if not calls:
            return None
        return xplane.total(xplane.subtract(calls, spans)) / 1e6 \
            / ctx["steps"]
    if report == "idle_ms_per_step":
        idle = xplane.idle_gaps_by_span(
            trace, trace.devices[0], (lo, hi), ANCHOR, span_pattern(names))
        if params.get("span") is None:
            return 1e3 * idle.get(xplane.NO_SPAN, 0.0) / ctx["steps"]
        rx = re.compile(params["span"])
        if not any(rx.search(e[2]) for e in spans):
            return None
        return 1e3 * sum(s for name, s in idle.items()
                         if name != xplane.NO_SPAN and rx.search(name)) \
            / ctx["steps"]
    if report == "fixed_ms_per_call":
        calls = sum(1 for e in spans if e[2] == FIXED[0])
        if not calls:
            return None
        fixed = xplane.subtract([e for e in spans if e[2] in FIXED],
                                [e for e in spans if e[2] == WAIT])
        return xplane.total(fixed) / 1e6 / calls
    raise ValueError(f"trace_host: unknown report {report!r}")
