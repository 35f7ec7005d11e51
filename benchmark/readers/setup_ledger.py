"""Start-up as the program's own ledger has it: set-up spans and JAX's compile
events by program and stage (``swiftmpi_tpu/obs/costs.py``, PR 52).

The one thing of the program this reader touches is
``swiftmpi_tpu.obs.setup_report()``, called in the run's own process after
the window; where ``obs`` has no such function (a commit before PR 52) every
report is ``None`` and the metric is left out of the line.  The ledger is kept
with telemetry on or off, so a ``--trace 0`` run holds the same report and
says it on one line of its log (``start-up: ...``); a metric is read in the
traced run, whose step program is the telemetry-on one.

``{"kind": "setup_ledger", "report": ..., "span": <regex>, "stage": <stage or
null>}``:

* ``"time_to_first_step"``: seconds from the first set-up span's start to the
  return of the first ``first_step`` span.
* ``"seconds"``: the set-up spans whose name matches ``span``, summed — a
  match inside another match is counted once, with the outer one.
* ``"stage_seconds"``: the compile events' own seconds of ``stage``
  (``trace`` | ``lower`` | ``compile`` | ``cache_read``; ``null``: all four)
  under a set-up span, at any depth, whose name matches ``span``.

``None`` where the ledger holds no span at all and, for the time to the first
step, before it returned.  A span that was never opened reads 0 seconds
(``kernel_import`` in a process that lowered no kernel: a CPU rehearsal) and
its stages ``None``; a stage with no event under a span that was opened reads 0.
"""

import re

STAGES = ("trace", "lower", "compile", "cache_read")


def program_report():
    """``obs.setup_report()``, or ``None`` where the program has none."""
    try:
        from swiftmpi_tpu import obs
    except ImportError:
        return None
    report = getattr(obs, "setup_report", None)
    return report() if report is not None else None


def _matches(rx, path) -> bool:
    return any(rx.search(name) for name in (path or "").split("/") if name)


def read(params: dict, ctx: dict):
    report = program_report()
    if report is None:
        return None
    kind = params["report"]
    if kind not in ("time_to_first_step", "seconds", "stage_seconds"):
        raise ValueError(f"setup_ledger: unknown report {kind!r}")
    if kind == "time_to_first_step":
        return report["time_to_first_step_s"]
    spans = report["spans"]
    if not spans:
        return None
    rx = re.compile(params["span"])
    if kind == "seconds":           # an inner match is inside an outer one
        return float(sum(
            s["seconds"] for s in spans if rx.search(s["name"])
            and not _matches(rx, s["path"].rpartition("/")[0])))
    stage = params.get("stage")
    if stage is not None and stage not in STAGES:
        raise ValueError(f"setup_ledger: unknown stage {stage!r}")
    if not any(rx.search(s["name"]) for s in spans):
        return None
    return float(sum(r["seconds"] for r in report["stages"]
                     if _matches(rx, r["span"])
                     and (stage is None or r["stage"] == stage)))
