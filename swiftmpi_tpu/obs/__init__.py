"""Unified telemetry plane (ISSUE 6).

One :class:`~swiftmpi_tpu.obs.registry.MetricsRegistry` for the whole
process — the transfer wire ledgers, the ``Throughput`` meter, pipeline
stats, fault events, checkpoint durations, and health probes all report
here instead of keeping private counters.  A
:class:`~swiftmpi_tpu.obs.recorder.StepRecorder` turns the registry into
a per-step JSONL time-series; :func:`span` wraps host-side hot-path
phases in ``profiler.annotate`` trace annotations AND a ``phase_ms``
histogram under the same name, so the TensorBoard trace and the JSONL
agree; :func:`named_scope` carries the same phase names into compiled
code (host timing is meaningless inside jit — the named scope shows up
in the device trace instead).

Everything is gated by ``[worker] telemetry:`` (see :func:`configure`).
The registry is process-global and created **disabled**: with telemetry
off, every instrument write and every ``span()`` is a single branch —
the measured-overhead test in tests/test_telemetry.py pins this down.

Module-level state exists because instruments are written from layers
with no config object in scope (transfer backends, the fault bus, the
health probes).  Tests get a clean slate via :func:`reset_for_tests`
(wired into tests/conftest.py); long-lived writers must therefore fetch
the registry through :func:`get_registry` (or re-check identity against
a cached reference) rather than caching it forever.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import time
from typing import Optional

import jax

from swiftmpi_tpu.obs.identity import process_ident, process_rank
from swiftmpi_tpu.obs.recorder import SCHEMA, SCHEMA_V, StepRecorder
from swiftmpi_tpu.obs.registry import (DEFAULT_BUCKETS_MS, MetricsRegistry,
                                       parse_series_key,
                                       quantile_from_buckets, series_key)
from swiftmpi_tpu.obs.collector import (FLEET_SCHEMA, FleetCollector,
                                        SupervisorLog, stream_filename)
from swiftmpi_tpu.obs import costs
from swiftmpi_tpu.obs import profiler as profiler_mod
from swiftmpi_tpu.obs.costs import CostCatalog, TrackedFn, get_catalog
from swiftmpi_tpu.cluster.bootstrap import ENV_FLEET_DIR
# aliased import: a bare ``from ...utils import profiler`` would shadow
# the ``obs.profiler`` SUBMODULE attribute on this package, silently
# rerouting ``from swiftmpi_tpu.obs import profiler`` to the host-side
# trace-annotation helpers (numerics.py and launch.py import the
# submodule that way)
from swiftmpi_tpu.utils import profiler as _host_profiler

__all__ = [
    "DEFAULT_BUCKETS_MS", "MetricsRegistry", "StepRecorder", "SCHEMA",
    "SCHEMA_V", "FLEET_SCHEMA", "FleetCollector", "SupervisorLog",
    "stream_filename", "series_key", "parse_series_key",
    "quantile_from_buckets", "process_ident", "process_rank",
    "get_registry", "set_enabled", "reset_for_tests", "span",
    "setup_span", "setup_report", "log_setup_once",
    "named_scope", "scope_prefix", "current_scope_prefix", "configure",
    "install_recorder", "uninstall_recorder",
    "get_recorder", "record_step", "CostCatalog", "TrackedFn",
    "get_catalog", "get_profiler", "install_profiler",
    "uninstall_profiler", "get_tracer", "install_tracer",
    "uninstall_tracer",
]

_SCOPE_PREFIX = contextvars.ContextVar("smtpu_scope_prefix", default="")


def named_scope(name: str):
    """Named scope for *compiled* code — same phase names as :func:`span`,
    rendered into the device trace by XLA instead of timed on the host.
    Under :func:`scope_prefix` the name is entered with that prefix."""
    return jax.named_scope(_SCOPE_PREFIX.get() + name)


@contextlib.contextmanager
def scope_prefix(prefix: str):
    """While tracing under this, every :func:`named_scope` enters
    ``prefix + name``: a module built from layers that scope themselves
    (``route``, ``experts``, ...) books its copies of them under names of
    its own, which ``obs.catalog.DEVICE_SCOPES`` maps to the module's phase
    — the phase map credits the *innermost* known scope, so an enclosing
    scope alone would lose them.  Read with :func:`current_scope_prefix`
    by code whose backward pass is traced later (a ``custom_vjp``)."""
    token = _SCOPE_PREFIX.set(prefix)
    try:
        yield
    finally:
        _SCOPE_PREFIX.reset(token)


def current_scope_prefix() -> str:
    """The prefix :func:`scope_prefix` set for the code being traced."""
    return _SCOPE_PREFIX.get()

_REGISTRY = MetricsRegistry(enabled=False)
_RECORDER: Optional[StepRecorder] = None
_PROFILER = None    # Optional[obs.profiler.ProfileSession]
_TRACER = None      # Optional[obs.trace.WindowTracer]


def get_registry() -> MetricsRegistry:
    """The process-global registry (disabled unless telemetry is on)."""
    return _REGISTRY


def set_enabled(on: bool) -> MetricsRegistry:
    _REGISTRY.enabled = bool(on)
    # telemetry on: tracked jits remember their abstract call signature,
    # which is all obs.costs.phase_map needs after the window
    costs.get_catalog().signatures = bool(on)
    return _REGISTRY


def reset_for_tests() -> MetricsRegistry:
    """Swap in a fresh disabled registry and drop any installed recorder.

    Cached instrument handles bound to the old registry keep working but
    write into the discarded object — hence writers re-check
    ``get_registry()`` identity (see ``Transfer._obs_state``)."""
    global _REGISTRY, _RECORDER, _PROFILER, _TRACER
    _REGISTRY = MetricsRegistry(enabled=False)
    _RECORDER = None
    _PROFILER = None
    uninstall_tracer()
    costs.reset_for_tests()
    return _REGISTRY


# -- named spans ------------------------------------------------------------

class _NullSpan:
    """Returned when telemetry is off: a shared, stateless no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def drop(self):
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """Host span = TraceAnnotation + ``phase_ms{phase=<name>}`` sample.
    The bookkeeping (the histogram's lookup and its sample) runs inside
    the annotation, so two spans one after the other leave between them
    only the calls that make them."""

    __slots__ = ("_reg", "_name", "_hist", "_ann", "_t0")

    def __init__(self, reg, name: str, attrs: dict):
        self._reg = reg
        self._name = name
        self._ann = _host_profiler.annotate(name, **attrs)

    def __enter__(self):
        self._ann.__enter__()
        self._hist = self._reg.histogram("phase_ms", phase=self._name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt_ms = (time.perf_counter() - self._t0) * 1e3
        if self._hist is not None:
            self._hist.observe(dt_ms)
        self._ann.__exit__(*exc)
        return False

    def drop(self):
        """Leave no ``phase_ms`` sample for this span (an end-of-stream
        ``input_wait`` fetched no item: one sample per item)."""
        self._hist = None


def span(name: str, **attrs):
    """Named host-phase span: ``with obs.span("render"): ...``.

    Telemetry off -> a shared no-op context (one branch, no allocation).
    On -> a ``jax.profiler.TraceAnnotation`` plus a sample in the
    ``phase_ms{phase=<name>}`` histogram, so the trace viewer and
    ``telemetry_report.py`` see the same phase under the same name.
    ``attrs`` go to the annotation alone (``dispatch`` carries ``step=``
    and ``steps=``, so a fused group and its device programs match by
    number); the histogram's labels stay ``phase=<name>``.
    Names are declared in ``obs.catalog.HOST_SPANS``.
    Only meaningful OUTSIDE jit — use :func:`named_scope` inside.
    """
    reg = _REGISTRY
    if not reg.enabled:
        return _NULL_SPAN
    return _Span(reg, name, attrs)


# -- set-up spans (the start-up ledger, obs/costs.py) ------------------------

setup_span = costs.setup_span
setup_report = costs.setup_report
log_setup_once = costs.log_setup_once


# -- recorder install point -------------------------------------------------

def install_recorder(rec: StepRecorder) -> StepRecorder:
    """Make ``rec`` the recorder :func:`record_step` feeds.  Layers with
    no config in scope (Trainer.step) report steps through the global."""
    global _RECORDER
    _RECORDER = rec
    return rec


def uninstall_recorder() -> Optional[StepRecorder]:
    global _RECORDER
    rec, _RECORDER = _RECORDER, None
    return rec


def get_recorder() -> Optional[StepRecorder]:
    return _RECORDER


def record_step(n: int = 1) -> None:
    """Account ``n`` consumed train steps on the installed recorder (a
    fused scan group counts its whole length) and the installed profiler
    session (ISSUE 14 triggered windows).  No-op when neither exists."""
    rec = _RECORDER
    if rec is not None:
        rec.on_steps(n)
    prof = _PROFILER
    if prof is not None:
        prof.on_step(n)
    tr = _TRACER
    if tr is not None:
        tr.on_step(n)


# -- profiler-session install point (obs/profiler.py) -----------------------

def install_profiler(sess):
    """Make ``sess`` the ProfileSession :func:`record_step` feeds."""
    global _PROFILER
    _PROFILER = sess
    return sess


def uninstall_profiler():
    global _PROFILER
    sess, _PROFILER = _PROFILER, None
    return sess


def get_profiler():
    return _PROFILER


# -- wire-tracer install point (obs/trace.py) -------------------------------

def install_tracer(tr, crash_flush: bool = True):
    """Make ``tr`` the WindowTracer the transfer ledgers and
    :func:`record_step` feed.  ``crash_flush`` enrolls it in the
    recorder module's atexit + fatal-signal hooks so a killed rank
    still leaves a flight-recorder dump behind."""
    global _TRACER
    _TRACER = tr
    if crash_flush:
        from swiftmpi_tpu.obs import recorder as recorder_mod
        recorder_mod._CRASH_RECORDERS.add(tr)
        recorder_mod._install_crash_hooks()
    return tr


def uninstall_tracer():
    """Clean teardown: detach the tracer WITHOUT dumping (a crash dump
    from a normal exit would be noise) and drop its crash enrollment."""
    global _TRACER
    tr, _TRACER = _TRACER, None
    if tr is not None:
        from swiftmpi_tpu.obs import recorder as recorder_mod
        recorder_mod._CRASH_RECORDERS.discard(tr)
    return tr


def get_tracer():
    return _TRACER


# -- config gate ------------------------------------------------------------

def configure(config, run: str = "run",
              meta: Optional[dict] = None) -> Optional[StepRecorder]:
    """Arm the telemetry plane from ``[worker]`` / ``[obs]`` config.

    Knobs under ``[worker]``:

    * ``telemetry: 1``        — master switch (default 0 = everything off)
    * ``telemetry_path:``     — JSONL sink (default ``telemetry.jsonl``;
      empty string = ring buffer only, no file)
    * ``telemetry_every: K``  — record every K consumed steps (default 1)
    * ``telemetry_ring: N``   — ring-buffer retention (default 1024)
    * ``telemetry_flush: N``  — JSONL write-buffer size (default 64)

    Fleet knobs under ``[obs]`` (ISSUE 12):

    * ``fleet_dir:`` — shared fleet-telemetry directory; the
      ``SMTPU_FLEET_DIR`` environment variable (set by
      ``launch.py -fleet-dir``) overrides it.  A fleet dir ARMS
      telemetry even when ``[worker] telemetry`` is off — a launcher
      asking for fleet observability must not be silently ignored by a
      worker config that never mentions telemetry — and redirects the
      JSONL sink to ``<fleet_dir>/telemetry_r<rank>_p<pid>.jsonl`` so
      every process life gets its own stream for the
      :class:`FleetCollector` to merge.
    * ``heartbeat_s: S`` — proof-of-life cadence (default 2.0 in fleet
      mode, 0 = off otherwise).
    * ``crash_flush: 1`` — atexit + fatal-signal telemetry flush
      (default on; see recorder.py).

    Compiler/device-cost knobs under ``[obs]`` (ISSUE 14) are armed
    here too, INDEPENDENTLY of the recorder — the compile catalog
    persists ``runs/compile_catalog.json`` and the profiler session
    captures traces even when the JSONL sink is off:

    * ``costs: 1`` / ``costs_path`` / ``costs_memory`` — the compiled-
      program catalog (obs/costs.py; ``SMTPU_COSTS=1`` overrides).
    * ``profile_at`` / ``profile_steps`` / ``profile_dir`` /
      ``profile_trigger`` / ``profile_on_anomaly`` — triggered profiler
      windows (obs/profiler.py; ``SMTPU_PROFILE_AT`` overrides, set by
      ``launch.py -profile-at`` for every rank).

    Returns the installed :class:`StepRecorder`, or ``None`` when
    telemetry is off.  The caller owns ``close()`` (or use it as a
    context manager); close appends the summary line and uninstalls
    nothing — :func:`uninstall_recorder` is explicit.
    """
    g = config.get_or
    fleet_dir = os.environ.get(ENV_FLEET_DIR) or \
        g("obs", "fleet_dir", "").to_string()
    cat = costs.configure_costs(config, run=run)
    prof = _configure_profiler(config, fleet_dir)
    tr = _configure_tracer(config, fleet_dir)
    if cat is not None or prof is not None or tr is not None:
        # instruments must record even without a JSONL sink — the
        # catalog artifact, the capture summaries and the trace ring
        # still read them
        set_enabled(True)
    if not g("worker", "telemetry", 0).to_bool() and not fleet_dir:
        return None
    set_enabled(True)
    path = g("worker", "telemetry_path", "telemetry.jsonl").to_string()
    if fleet_dir:
        os.makedirs(fleet_dir, exist_ok=True)
        path = os.path.join(
            fleet_dir, stream_filename(process_rank(), os.getpid()))
    rec = StepRecorder(
        _REGISTRY,
        path=path or None,
        run=run,
        ring=g("worker", "telemetry_ring", 1024).to_int32(),
        flush_every=g("worker", "telemetry_flush", 64).to_int32(),
        every=g("worker", "telemetry_every", 1).to_int32(),
        meta=meta,
        heartbeat_s=g("obs", "heartbeat_s",
                      2.0 if fleet_dir else 0.0).to_float(),
        crash_flush=g("obs", "crash_flush", 1).to_bool(),
    )
    if tr is not None:
        # hot-key attribution + last-window gauges ride the step series
        rec.add_sampler(tr.sampler)
    return install_recorder(rec)


def _configure_profiler(config, fleet_dir: str):
    """Install a ProfileSession when any trigger path is armed: the
    ``profile_at`` knob (or its launcher env override), the fleet-dir
    trigger file (on by default in fleet mode — polling is one stat per
    second), or the numerics-anomaly hook.  None of them armed (the
    default) installs nothing — ``record_step`` stays recorder-only."""
    g = config.get_or
    at = g("obs", "profile_at", -1).to_int32()
    env_at = os.environ.get(profiler_mod.ENV_PROFILE_AT, "")
    if env_at:
        at = int(env_at)
    steps = g("obs", "profile_steps", 5).to_int32()
    env_steps = os.environ.get(profiler_mod.ENV_PROFILE_STEPS, "")
    if env_steps:
        steps = int(env_steps)
    trigger = bool(fleet_dir) and g("obs", "profile_trigger",
                                    1).to_bool()
    on_anomaly = g("obs", "profile_on_anomaly", 0).to_bool()
    if at < 0 and not trigger and not on_anomaly:
        return None
    sess = profiler_mod.ProfileSession(
        profile_dir=g("obs", "profile_dir",
                      os.path.join("runs", "profiles")).to_string(),
        steps=steps, profile_at=at,
        fleet_dir=fleet_dir if trigger else None,
        capture_on_anomaly=on_anomaly)
    return install_profiler(sess)


def _configure_tracer(config, fleet_dir: str):
    """Install a WindowTracer when ``[obs] trace`` is armed (default off
    — the transfer ledgers' host callbacks then never touch the trace
    plane and the key-reservoir tap stays out of the traced programs,
    which is the bit-identity contract the ON-vs-OFF tests pin).  Like
    every format-affecting knob, arming or clearing mid-run requires a
    step rebuild for the reservoir/EF taps to appear or vanish; the
    record/ledger plumbing itself follows the tracer live."""
    g = config.get_or
    if not g("obs", "trace", 0).to_bool():
        return None
    cur = get_tracer()
    if cur is not None:
        # repeated train() calls must not stack tracers: the old one
        # would stay enrolled in _CRASH_RECORDERS and dump a stale
        # "crash" ring at exit.  The installed instance follows the
        # run live; re-arming with different knobs needs an explicit
        # uninstall_tracer() first.
        return cur
    from swiftmpi_tpu.obs import trace as trace_mod
    tr = trace_mod.WindowTracer(
        trace_dir=g("obs", "trace_dir", "runs").to_string(),
        ring=g("obs", "trace_ring", 256).to_int32(),
        sample=g("obs", "trace_sample", 1).to_int32(),
        keys=g("obs", "trace_keys", 64).to_int32(),
        topk=g("obs", "trace_topk", 8).to_int32(),
        fleet_dir=fleet_dir or None,
        dump_on_anomaly=g("obs", "trace_on_anomaly", 1).to_bool())
    return install_tracer(
        tr, crash_flush=g("obs", "crash_flush", 1).to_bool())
