"""Compiled-program catalog: XLA cost/memory attribution + retrace
tracking (ISSUE 14).

Every jitted step/kernel the model builders and transfer backends
produce funnels through :func:`track`, which wraps the jit in a
:class:`TrackedFn`.  Disarmed (the default), the wrapper is a single
attribute check around the call — the jit object, its dispatch path and
its traced program are untouched, so a default-off run is bit-identical
to one built before this module existed.  Armed (``[obs] costs: 1`` or
``SMTPU_COSTS=1``), every *compile* event — a call of the handle during
which JAX lowered a program, as the start-up ledger below saw it — is
recorded three ways:

* ``compile/compiles{fn=}`` / ``compile/compile_ms{fn=}`` /
  ``compile/retraces{fn=}`` counters in the telemetry registry, so a
  retrace storm shows up in the JSONL stream and the budget gate, not
  just in ``tests/test_retrace_guard.py``; ``compile_ms`` is the sum of
  the stages' own durations (tracing, lowering, and the backend's compile
  or its read from the persistent cache) and holds no execution;
* XLA's own ``cost_analysis()`` (flops, bytes accessed — a cheap
  trace + StableHLO emit, no backend compile) and, gated by
  ``[obs] costs_memory``, ``memory_analysis()`` (argument/output/temp
  bytes from one extra backend compile) as ``compile/{flops,bytes,
  peak_bytes}{fn=}`` gauges;
* a crash-safe ``runs/compile_catalog.json`` (schema
  ``smtpu-costs/1``), rewritten atomically on every compile event, so
  bench rooflines and ``telemetry_report.py --compile`` can diff the
  measured numbers against the hand byte/FLOP models
  (:func:`CostCatalog.note_hand_model`).

Phase map (ISSUE 23).  The device trace names an event by its HLO
instruction (``fusion.24``, ``copy.141.remat3``); the compiled module's
text carries ``metadata={op_name="jit(step)/.../pull/gather"}`` for the
same names.  While telemetry is on (``[obs] costs`` need not be) a
handle remembers, on its first call, the *abstract* signature it was
called with — shapes, dtypes and committed shardings, never the arrays:
the state is donated and fills the chip — and :func:`phase_map` lowers
and compiles from that signature **on demand, after the window** (the
executable comes from the compile cache), parses the text once per
handle and returns ``{module, phase: {instruction: phase}, ...}`` over
the scope names of :data:`obs.catalog.DEVICE_SCOPES`.  Nothing on the
call path lowers, compiles or reads text.

Retrace semantics are **per handle**, matching the retrace-guard test:
one name may cover many jit objects (the w2v fused cache holds one per
group length, the tpu backend one per push signature) and each handle's
FIRST compile is expected; only a handle *tracing again* after a compile
it booked — genuine shape/dtype churn on one program — books a retrace.
A control-plane safe-point recompile builds fresh handles, so it books
compiles, never retraces.

Start-up ledger (ISSUE 52).  JAX times every stage of making a program
itself and names the function (``jax._src.dispatch.log_elapsed_time``
around tracing, lowering and the backend's compile; ``jax._src.compiler``
says when that last one was a read from the persistent cache).  This
module listens, once a process, and books each event — program, stage
(:data:`STAGES`), its own seconds, and the innermost *set-up span*
(``obs.setup_span``, names in ``obs.catalog.SETUP_SPANS``) open on the
thread — in :class:`SetupLedger`, whether telemetry is on or not: a
cached dispatch emits no such event, so a step pays nothing.  A tracked
handle's first call runs in the set-up span ``first_step``
(:meth:`TrackedFn.__call__`: the one place that opens it).
:func:`setup_report` is the read side (the benchmark's ``entry.*``
metrics), :func:`log_setup_once` the one line in the program's log.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

import jax.monitoring

from swiftmpi_tpu.obs.catalog import DEVICE_SCOPES, UNSCOPED
from swiftmpi_tpu.utils.logger import get_logger

log = get_logger(__name__)

#: catalog artifact schema tag (``runs/compile_catalog.json``).
COSTS_SCHEMA = "smtpu-costs/1"
COSTS_SCHEMA_PREFIX = "smtpu-costs/"

#: env override that arms the catalog without a config edit.
ENV_COSTS = "SMTPU_COSTS"


class CostCatalog:
    """Per-process compile-event ledger.  Created disarmed; armed by
    :func:`configure_costs`.
    Writers go through :func:`get_catalog` each call — the instance is
    swapped by :func:`reset_for_tests`, like the metrics registry."""

    def __init__(self, enabled: bool = False,
                 path: Optional[str] = None,
                 memory: bool = True, analyze_max: int = 1,
                 run: str = "run"):
        #: the ONE attribute ``TrackedFn.__call__`` checks when nothing
        #: is armed: true unless ``enabled`` (compile events) or
        #: ``signatures`` (telemetry on: handles remember their abstract
        #: signature for :func:`phase_map`) is set
        self.idle = True
        self._enabled = self._signatures = False
        self.enabled = enabled
        self.path = path
        #: run memory_analysis (one extra backend compile per analyzed
        #: handle) — [obs] costs_memory
        self.memory = memory
        #: handles analyzed per fn name (lower+cost_analysis per handle
        #: is cheap but not free; the first handle is representative)
        self.analyze_max = analyze_max
        self.run = run
        self._lock = threading.Lock()
        self._fns: Dict[str, dict] = {}     # guarded-by: _lock
        self._analyzed: Dict[str, int] = {}  # guarded-by: _lock
        #: handles that remembered a signature, by catalog name, oldest
        #: first (weak: a rebuilt step drops its old handle)
        self._handles: Dict[str, List[weakref.ref]] = {}  # guarded-by: _lock

    @property
    def enabled(self) -> bool:
        return self._enabled

    @enabled.setter
    def enabled(self, on: bool) -> None:
        self._enabled = bool(on)
        self.idle = not (self._enabled or self._signatures)

    @property
    def signatures(self) -> bool:
        return self._signatures

    @signatures.setter
    def signatures(self, on: bool) -> None:
        self._signatures = bool(on)
        self.idle = not (self._enabled or self._signatures)

    # -- abstract signatures + phase maps ----------------------------------
    def remember(self, handle: "TrackedFn", args, kwargs) -> None:
        """First call of ``handle`` while signatures are wanted: keep the
        call's abstract signature on it, and the handle findable by name."""
        sig = _abstract_signature(args, kwargs)
        if sig is None:     # called under a trace: inlined, not a program
            return
        handle._sig = sig
        with self._lock:
            live = [r for r in self._handles.get(handle.name, ())
                    if r() is not None]     # rebuilt steps leave dead refs
            self._handles[handle.name] = live + [weakref.ref(handle)]

    def handle(self, name: str) -> Optional["TrackedFn"]:
        """The newest live handle of ``name`` that remembered a signature
        (one name may cover several: fused tail lengths, rebuilt steps —
        the newest is the one the last window ran)."""
        with self._lock:
            refs = list(self._handles.get(name, ()))
        for ref in reversed(refs):
            h = ref()
            if h is not None:
                return h
        return None

    def phase_map(self, name: str) -> Optional[dict]:
        h = self.handle(name)
        return h.phase_map() if h is not None else None

    def phase_maps(self) -> Dict[str, dict]:
        """``{hlo module name: phase map}`` of every tracked name that has
        one — what a trace reduction joins device events with."""
        with self._lock:
            names = list(self._handles)
        maps = (self.phase_map(name) for name in names)
        return {m["module"]: m for m in maps if m is not None}

    # -- the compile event -------------------------------------------------
    def on_compile(self, name: str, fn, args, kwargs, dt_ms: float,
                   retrace: bool, steps_per_call: int = 1) -> None:
        """Book one compile of ``fn`` (the unwrapped jit) under ``name``.
        ``retrace``: this very handle traced again after a compile it
        booked.  ``dt_ms`` is what JAX timed of the stages of making the
        program (tracing — inner jits' with it —, lowering, and the
        backend's compile or its read from the persistent cache), each
        its own duration; the call's execution is not in it."""
        with self._lock:
            e = self._fns.get(name)
            if e is None:
                e = self._fns[name] = {
                    "fn": name, "compiles": 0, "retraces": 0,
                    "compile_ms_total": 0.0, "last_compile_ms": 0.0,
                    "steps_per_call": steps_per_call,
                }
            e["compiles"] += 1
            e["compile_ms_total"] += dt_ms
            e["last_compile_ms"] = dt_ms
            e["steps_per_call"] = steps_per_call
            if retrace:
                e["retraces"] += 1
            n_analyzed = self._analyzed.get(name, 0)
            analyze = n_analyzed < self.analyze_max
            if analyze:
                self._analyzed[name] = n_analyzed + 1
        from swiftmpi_tpu import obs
        reg = obs.get_registry()
        reg.counter("compile/compiles", fn=name).inc()
        reg.counter("compile/compile_ms", fn=name).inc(dt_ms)
        if retrace:
            reg.counter("compile/retraces", fn=name).inc()
        if analyze:
            a = _analyze(fn, args, kwargs, memory=self.memory)
            if a:
                with self._lock:
                    self._fns[name].update(a)
                if a.get("flops"):
                    reg.gauge("compile/flops", fn=name).set(a["flops"])
                if a.get("bytes_accessed"):
                    reg.gauge("compile/bytes",
                              fn=name).set(a["bytes_accessed"])
                if a.get("peak_bytes"):
                    reg.gauge("compile/peak_bytes",
                              fn=name).set(a["peak_bytes"])
        self._persist()

    # -- hand-model drift --------------------------------------------------
    def note_hand_model(self, name: str, flops: Optional[float] = None,
                        bytes_accessed: Optional[float] = None) -> None:
        """Record the hand byte/FLOP model's *per-call* prediction for
        ``name`` so reports can print measured-vs-model drift.  Callers
        with per-step models multiply by the fn's steps_per_call."""
        with self._lock:
            e = self._fns.setdefault(name, {
                "fn": name, "compiles": 0, "retraces": 0,
                "compile_ms_total": 0.0, "last_compile_ms": 0.0,
                "steps_per_call": 1,
            })
            if flops is not None:
                e["hand_flops"] = float(flops)
            if bytes_accessed is not None:
                e["hand_bytes"] = float(bytes_accessed)
        self._persist()

    # -- reads -------------------------------------------------------------
    def entry(self, name: str) -> Optional[dict]:
        with self._lock:
            e = self._fns.get(name)
            return dict(e) if e is not None else None

    def entries(self) -> Dict[str, dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._fns.items()}

    def snapshot(self) -> dict:
        """The ``smtpu-costs/1`` document: per-fn compile/retrace
        counts, measured flops/bytes, and drift percentages wherever a
        hand model was noted next to a measurement."""
        fns = self.entries()
        for e in fns.values():
            _add_drift(e)
        return {"schema": COSTS_SCHEMA, "run": self.run,
                "ts": time.time(), "fns": fns}

    # -- persistence ---------------------------------------------------
    def _persist(self) -> None:
        path = self.path
        if not path:
            return
        doc = self.snapshot()
        try:
            parent = os.path.dirname(path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass    # artifact write must never take down training


def _add_drift(e: dict) -> None:
    """measured-vs-hand drift: positive = the hand model OVERestimates."""
    f, hf = e.get("flops"), e.get("hand_flops")
    if f and hf is not None:
        e["flops_drift_pct"] = round(100.0 * (hf - f) / f, 1)
    b, hb = e.get("bytes_accessed"), e.get("hand_bytes")
    if b and hb is not None:
        e["bytes_drift_pct"] = round(100.0 * (hb - b) / b, 1)


def _analyze(fn, args, kwargs, memory: bool = True) -> dict:
    """Best-effort XLA analysis of one compiled handle.  ``lower()`` is
    shape-only, so it is safe even after the triggering call donated
    its buffers; ``cost_analysis()`` on the Lowered needs no backend
    compile.  ``memory_analysis()`` does one — gated by ``memory``."""
    out: Dict[str, Any] = {}
    lower = getattr(fn, "lower", None)
    if lower is None:
        return out
    try:
        lowered = lower(*args, **kwargs)
    except Exception:
        return out
    try:
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):    # Compiled-level shape
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            f = ca.get("flops")
            b = ca.get("bytes accessed")
            if f is not None and float(f) > 0:
                out["flops"] = float(f)
            if b is not None and float(b) > 0:
                out["bytes_accessed"] = float(b)
    except Exception:
        pass
    if memory:
        try:
            ms = lowered.compile().memory_analysis()
            arg = int(getattr(ms, "argument_size_in_bytes", 0))
            outb = int(getattr(ms, "output_size_in_bytes", 0))
            tmp = int(getattr(ms, "temp_size_in_bytes", 0))
            alias = int(getattr(ms, "alias_size_in_bytes", 0))
            out["argument_bytes"] = arg
            out["output_bytes"] = outb
            out["temp_bytes"] = tmp
            out["alias_bytes"] = alias
            # live-at-once upper bound: donated (aliased) buffers are
            # not double-counted
            out["peak_bytes"] = max(arg + outb + tmp - alias, 0)
        except Exception:
            pass
    return out


# -- start-up ledger ------------------------------------------------------------

#: JAX's monitoring events the ledger listens for.  The three durations
#: come from ``jax._src.dispatch.log_elapsed_time`` with the function's
#: name (a scalar of the same name as each starts, a time span as it
#: ends); the backend's includes a read from the persistent cache, which
#: ``jax._src.compiler`` announces inside it with the fourth, a bare event
#: (its ``cache_retrieval_time_sec`` is inside that duration too and is
#: not listened for: the duration brackets the key's hash and the read)
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: what making a program is made of: tracing the function to a jaxpr (an
#: inner jit's tracing is its own event, inside its caller's), lowering
#: the jaxpr to StableHLO (Pallas kernels to Mosaic with it), then the
#: backend's compile — or, in its place, the executable's read from the
#: persistent cache
STAGES = ("trace", "lower", "compile", "cache_read")
_STAGE_OF = {TRACE_EVENT: "trace", LOWER_EVENT: "lower",
             BACKEND_EVENT: "compile"}


class _PerThread(threading.local):
    """A thread's place in the ledger."""

    def __init__(self):
        self.stack = []           # indices of the spans open, outermost first
        self.cache_hit = False    # a cache hit awaits its backend event
        self.stages = []          # [start, seconds inside] of stages open
        self.watch = None         # TrackedFn's armed call collects here


class SetupLedger:
    """What start-up was made of, from inside the program: set-up spans
    (``obs.setup_span``; name, parent, start, end) and JAX's compile
    events by program and stage, each with the innermost set-up span
    open on its thread.  Kept in memory whether telemetry is on or not —
    a few clock reads an event — and bounded: every event until the first
    ``first_step`` span returns (*start-up*), after that only events
    under a set-up span (a rebuilt step, a new fused length); an event is
    folded as it arrives into the row of its (span, program, stage) — a
    step's tracing alone is thousands of inner jits' — and at most
    :attr:`LIMIT` spans and rows are kept.  Swapped by
    :func:`reset_for_tests` like the catalog."""

    #: spans and rows kept; what comes after is counted in ``dropped``
    LIMIT = 4096

    def __init__(self):
        #: ``[name, parent's index or None, start, end or None]``, in
        #: the order opened, on ``time.perf_counter``'s clock
        self.spans: List[list] = []
        #: ``{(span's index or None, program, stage, of start-up):
        #: [count, own seconds]}``: an event's own seconds leave out the
        #: stages inside it (an inner jit's tracing), events of their own
        self.rows: Dict[tuple, list] = {}
        self.dropped = 0
        #: the return of the first ``first_step`` span: start-up's end
        self.first_step_end: Optional[float] = None
        self.logged = False            # log_setup_once said its line
        #: guards ``spans``, ``rows`` and ``dropped`` (a span's index is
        #: its place in ``spans``: appended and read in one hold); a
        #: thread's own place (:class:`_PerThread`) needs none
        self._lock = threading.Lock()
        self._thread = _PerThread()

    # -- spans ---------------------------------------------------------------
    def open(self, name: str) -> Optional[int]:
        stack = self._thread.stack
        with self._lock:
            if len(self.spans) >= self.LIMIT:
                self.dropped += 1
                return None
            self.spans.append([name, stack[-1] if stack else None,
                               time.perf_counter(), None])
            index = len(self.spans) - 1
        stack.append(index)
        return index

    def close(self, index: Optional[int]) -> None:
        if index is None:
            return
        span = self.spans[index]
        span[3] = time.perf_counter()
        stack = self._thread.stack
        if index in stack:          # spans close innermost first
            del stack[stack.index(index):]
        if span[0] == "first_step" and self.first_step_end is None:
            self.first_step_end = span[3]

    # -- JAX's events --------------------------------------------------------
    def on_cache_hit(self) -> None:
        self._thread.cache_hit = True

    def on_stage_start(self, start: float) -> None:
        """A stage begins on this thread (JAX says so as it starts its
        clock): what begins and ends before it ends is inside it."""
        self._thread.stages.append([start, 0.0])

    def in_stage(self) -> bool:
        """JAX is tracing or lowering a program on this thread: a jit
        called now is inlined into that program."""
        return bool(self._thread.stages)

    def on_stage(self, stage: str, start: float, end: float,
                 program: str) -> None:
        """One of JAX's stage durations, ``start`` and ``end`` on JAX's
        clock (``time.time``): kept on the ledger's, ending now.  Its own
        seconds leave out the stages inside it — an inner jit's tracing,
        a program a trace ran eagerly — which are events of their own."""
        t = self._thread
        inside = 0.0
        for i in range(len(t.stages) - 1, -1, -1):
            if t.stages[i][0] == start:     # its frame: JAX gave both
                inside = t.stages[i][1]     # the same start
                del t.stages[i:]
                break
        if t.stages:
            t.stages[-1][1] += end - start
        own = max(end - start - inside, 0.0)
        if stage == "compile" and t.cache_hit:
            stage, t.cache_hit = "cache_read", False
        if t.watch is not None:
            t.watch.append((program, stage, own))
        startup = self.first_step_end is None
        if not startup and not t.stack:
            return                # start-up is over: spans' events only
        key = (t.stack[-1] if t.stack else None, program, stage, startup)
        with self._lock:
            row = self.rows.get(key)
            if row is None:
                if len(self.rows) >= self.LIMIT:
                    self.dropped += 1
                    return
                row = self.rows[key] = [0, 0.0]
            row[0] += 1
            row[1] += own

    @contextlib.contextmanager
    def watch(self):
        """The stage events of the calling thread inside the block —
        ``(program, stage, own seconds)`` — kept by the ledger or not."""
        t = self._thread
        held, t.watch = t.watch, []
        try:
            yield t.watch
        finally:
            if held is not None:
                held.extend(t.watch)
            t.watch = held

    # -- the read side -------------------------------------------------------
    def report(self) -> dict:
        """See :func:`setup_report`."""
        with self._lock:
            spans = [list(span) for span in self.spans]
            folded = [(key, list(row)) for key, row in self.rows.items()]
        now = time.perf_counter()
        origin = spans[0][2] if spans else None
        first = self.first_step_end
        paths, inner = [], [0.0] * len(spans)
        for name, parent, start, end in spans:
            paths.append(name if parent is None
                         else f"{paths[parent]}/{name}")
            if parent is not None:
                inner[parent] += (end or now) - start
        rows: Dict[tuple, list] = {}      # a path's spans together
        for (span, program, stage, startup), (n, secs) in folded:
            row = rows.setdefault((None if span is None else paths[span],
                                   program, stage, startup), [0, 0.0])
            row[0] += n
            row[1] += secs
        return {
            "spans": [{"name": name, "path": paths[i], "parent": parent,
                       "start_s": start - origin,
                       "seconds": (end or now) - start,
                       "self_seconds": (end or now) - start - inner[i],
                       "open": end is None}
                      for i, (name, parent, start, end) in enumerate(spans)],
            "stages": [{"span": span, "program": program, "stage": stage,
                        "startup": startup, "count": n, "seconds": secs}
                       for (span, program, stage, startup), (n, secs)
                       in rows.items()],
            "time_to_first_step_s": None if first is None or origin is None
            else first - origin,
            "dropped": self.dropped}


_LEDGER = SetupLedger()


def get_ledger() -> SetupLedger:
    """The process-global start-up ledger (:func:`reset_for_tests` swaps
    it: writers fetch it each time)."""
    return _LEDGER


class _SetupSpan:
    """Set-up span = TraceAnnotation + a record in the start-up ledger
    (name, parent, start, end), telemetry on or off: it leaves no
    ``phase_ms`` sample and touches no registry series."""

    __slots__ = ("_name", "_ann", "_ledger", "_index")

    def __init__(self, name: str):
        self._name = name
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        self._ledger = _LEDGER
        self._index = self._ledger.open(self._name)
        return self

    def __exit__(self, *exc):
        self._ledger.close(self._index)
        self._ann.__exit__(*exc)
        return False


def setup_span(name: str):
    """Named span of start-up work: ``with obs.setup_span("model_build"):``.

    Recorded whether telemetry is on or not (a model is built once; a
    step opens none) with its parent — the set-up span open on the
    thread — and, while it is open, every compile event of the thread is
    booked under it (:func:`setup_report`).  Under a running profiler it
    is a ``TraceAnnotation`` on the trace's clock, like ``obs.span``.
    Names are declared in ``obs.catalog.SETUP_SPANS``."""
    return _SetupSpan(name)


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    stage = _STAGE_OF.get(event)
    if stage is not None:
        # tracing says ``step``, lowering and the backend ``jit(step)``
        name = str(kw.get("fun_name", "?"))
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        _LEDGER.on_stage(stage, start, end, name)


def _on_scalar(event: str, value: float, **_kw) -> None:
    if event in _STAGE_OF:        # a stage's start, as JAX reads its clock
        _LEDGER.on_stage_start(value)


def _on_event(event: str, **_kw) -> None:
    if event == CACHE_HIT_EVENT:
        _LEDGER.on_cache_hit()


# once a process, at import: the listeners find the ledger of the day
jax.monitoring.register_scalar_listener(_on_scalar)
jax.monitoring.register_event_time_span_listener(_on_time_span)
jax.monitoring.register_event_listener(_on_event)


def setup_report() -> dict:
    """The start-up ledger, reduced:

    * ``spans``: every set-up span in the order opened — ``name``,
      ``path`` (``model_build/table_create``), ``parent`` (an index into
      this list, or ``None``), ``start_s`` after the first span's start,
      ``seconds``, ``self_seconds`` (less its children) and ``open``;
    * ``stages``: the compile events by ``span`` (the path of the
      innermost set-up span open on the event's thread, or ``None``),
      ``program`` (JAX's ``fun_name``), ``stage`` (:data:`STAGES`) and
      ``startup`` (it arrived before the first ``first_step`` returned)
      — ``count`` and ``seconds``, each event its own duration;
    * ``time_to_first_step_s``: from the first set-up span's start to the
      return of the first ``first_step`` span (``None`` until then);
    * ``dropped``: spans and events past :attr:`SetupLedger.LIMIT`.
    """
    return _LEDGER.report()


def setup_line(report: dict) -> str:
    """:func:`setup_report` on one line: spans by path (seconds, and the
    count where a path opened more than once), the stages' seconds and
    counts over every program and under ``first_step``, the programs of
    start-up outside ``first_step``, and the programs the backend compiled
    (no read from the persistent cache), slowest first."""
    by_path: Dict[str, list] = {}
    for s in report["spans"]:
        row = by_path.setdefault(s["path"], [0, 0.0])
        row[0] += 1
        row[1] += s["seconds"]

    def stage_text(rows) -> str:
        out = []
        for stage in STAGES:
            hit = [r for r in rows if r["stage"] == stage]
            out.append(f"{stage} {sum(r['seconds'] for r in hit):.2f}s/"
                       f"{sum(r['count'] for r in hit)}")
        return " ".join(out)

    def under_first_step(r) -> bool:
        return "first_step" in (r["span"] or "").split("/")

    rows = report["stages"]
    small = [r for r in rows if r["startup"] and not under_first_step(r)]
    cold: Dict[str, float] = {}
    for r in rows:
        if r["stage"] == "compile":
            cold[r["program"]] = cold.get(r["program"], 0.0) + r["seconds"]
    ttfs = report["time_to_first_step_s"]
    return (
        "start-up: " + ("no step yet" if ttfs is None
                        else f"{ttfs:.2f}s to the first step's return")
        + "; spans " + " ".join(
            f"{path}={secs:.2f}s" + (f"/{n}" if n > 1 else "")
            for path, (n, secs) in by_path.items())
        + "; all programs " + stage_text(rows)
        + "; under first_step " + stage_text(
            [r for r in rows if under_first_step(r)])
        + f"; before it and outside "
        f"{sum(r['count'] for r in small if r['stage'] == 'lower')} programs "
        f"{sum(r['seconds'] for r in small):.2f}s"
        + "; compiled by the backend: " + (" ".join(
            f"{name}={secs:.2f}s" for name, secs in sorted(
                cold.items(), key=lambda kv: -kv[1])[:4]) or "none")
        + (f"; {report['dropped']} records dropped"
           if report["dropped"] else ""))


def log_setup_once() -> None:
    """Say the start-up ledger in the log, once a process: ``train()`` and
    ``Trainer.run()`` call this as they return, and the first call whose
    ledger has a closed ``first_step`` speaks."""
    ledger = _LEDGER
    if ledger.logged or ledger.first_step_end is None:
        return
    ledger.logged = True
    log.info("%s", setup_line(ledger.report()))


class TrackedFn:
    """The funnel wrapper around one jit handle.

    Call path invariant: the wrapped jit is ALWAYS the callee — armed
    or not, cached or first call — so arming cannot change dispatch
    behavior, only observe it.  A handle's first call — tracing,
    lowering, the compile or the read from the persistent cache, and the
    first execution queued — runs in the set-up span ``first_step``, so
    the compile events inside are that program's whatever name JAX gives
    it; a first call made while JAX traces another program on the thread
    is inlined there and opens none.  After it a call costs one
    attribute read more.  Compile detection is JAX's own stage
    events on the calling thread during the call (the start-up ledger's
    listeners): a call in which a program was lowered compiled, and one
    that also traced, on a handle that compiled before, retraced.  A
    handle called under a trace is inlined there and lowers nothing; a
    plain callable emits no event: neither books.

    Unknown attributes forward to the wrapped fn, so ``lower()`` /
    ``_cache_size()`` callers don't need to know about the wrapper.
    """

    __slots__ = ("_fn", "name", "steps_per_call", "unrun", "_compiles",
                 "_sig", "_phase_map", "__weakref__")

    def __init__(self, name: str, fn, steps_per_call: int = 1):
        self._fn = fn
        self.name = name
        self.steps_per_call = max(int(steps_per_call), 1)
        #: not called yet: the call that comes is its ``first_step``
        self.unrun = True
        self._compiles = 0
        self._sig = None          # (args, kwargs) of ShapeDtypeStructs
        self._phase_map = _NOT_COMPUTED

    def __call__(self, *args, **kwargs):
        if self.unrun:                # the call after its build
            self.unrun = False
            if not _LEDGER.in_stage():
                with _SetupSpan("first_step"):
                    return self(*args, **kwargs)
        cat = _CATALOG
        if cat.idle:
            return self._fn(*args, **kwargs)
        if self._sig is None:
            cat.remember(self, args, kwargs)
        if not cat.enabled:
            return self._fn(*args, **kwargs)
        with _LEDGER.watch() as seen:
            out = self._fn(*args, **kwargs)
        stages = {stage for _program, stage, _own in seen}
        if "lower" in stages:
            retrace = self._compiles > 0 and "trace" in stages
            self._compiles += 1
            cat.on_compile(self.name, self._fn, args, kwargs,
                           1e3 * sum(own for _p, _s, own in seen), retrace,
                           self.steps_per_call)
        return out

    def phase_map(self) -> Optional[dict]:
        """See :func:`phase_map`; computed once per handle."""
        if self._phase_map is _NOT_COMPUTED:
            self._phase_map = _compile_phase_map(self.name, self._fn,
                                                 self._sig)
        return self._phase_map

    def __getattr__(self, attr):
        return getattr(self._fn, attr)

    def __repr__(self) -> str:
        return f"TrackedFn({self.name!r}, {self._fn!r})"


# -- phase map ----------------------------------------------------------------

_NOT_COMPUTED = object()


def _abstract_signature(args, kwargs):
    """``(args, kwargs)`` with every array leaf replaced by its
    ``ShapeDtypeStruct`` (committed arrays keep their sharding, so the
    lowering is the executed one); other leaves (Python scalars, static
    values) stay.  Holds no device buffer.  ``None`` for a call made
    under a trace (a tracked jit nested in another is inlined there)."""
    import jax
    import numpy as np

    leaves = jax.tree_util.tree_leaves((args, kwargs))
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return None

    def abstract(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=x.sharding if x.committed else None)
        if isinstance(x, np.ndarray):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(abstract, (tuple(args), dict(kwargs)))


_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = (.*)$")
_HLO_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE_PART = re.compile(r"^(?:\w+\()*([\w.\-]+)\)*$")
#: opcodes that run nothing of their own: counted neither as
#: instructions nor as unscoped
_HLO_NO_WORK = frozenset({"parameter", "constant", "tuple",
                          "get-tuple-element", "bitcast"})


def phase_of(op_name: str) -> Optional[str]:
    """The innermost known phase in an ``op_name`` path
    (``jit(step)/jit(main)/apply/dedup/sort`` -> ``dedup``); a transform
    wraps the scope it was applied under (``vmap(pull)``)."""
    for part in reversed(op_name.split("/")):
        m = _SCOPE_PART.match(part)
        if m and m.group(1) in DEVICE_SCOPES:
            return DEVICE_SCOPES[m.group(1)]
    return None


def parse_hlo_phases(text: str) -> dict:
    """Phase of every instruction of a compiled module's text.

    An instruction's phase is :func:`phase_of` its own ``op_name``; a
    ``fusion`` takes the phase most of its fused instructions carry (ties:
    the root's, else the first seen), since the trace shows the fusion and
    never its parts.  Instructions under no phase map to ``"unscoped"`` —
    none is guessed from a shape.  ``scoped`` counts the instructions,
    fused ones included, that carry a phase at all."""
    module = None
    # computation -> [(name, is root, opcode, own phase, called computation)]
    comps: Dict[str, list] = {}
    cur = None
    for line in text.splitlines():
        if module is None:
            m = _HLO_MODULE.match(line)
            if m:
                module = m.group(1)
            continue
        m = _HLO_COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        if cur is None:
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _HLO_INSTRUCTION.match(line)
        if m:
            rhs = m.group(3)
            op = _HLO_OPCODE.search(rhs)
            on = _HLO_OP_NAME.search(rhs)
            calls = _HLO_CALLS.search(rhs)
            cur.append((m.group(2), bool(m.group(1)),
                        op.group(1) if op else "",
                        phase_of(on.group(1)) if on else None,
                        calls.group(1) if calls else None))

    def fused_phase(comp):
        votes: Dict[str, int] = {}
        root = None
        for _name, is_root, _op, ph, _calls in comps.get(comp, ()):
            if ph is not None:
                votes[ph] = votes.get(ph, 0) + 1
                if is_root:
                    root = ph
        if not votes:
            return None
        best = max(votes.values())
        tied = [ph for ph, n in votes.items() if n == best]
        return root if root in tied else tied[0]

    fused = {calls for body in comps.values()
             for _n, _r, op, _p, calls in body if op == "fusion" and calls}
    phase: Dict[str, str] = {}
    n_instr = n_unscoped = n_scoped = 0
    for comp, body in comps.items():
        for name, _root, op, ph, calls in body:
            n_scoped += ph is not None
            if comp in fused:
                continue          # never a trace event of its own
            if op == "fusion" and calls:
                ph = fused_phase(calls) or ph
            phase[name] = ph or UNSCOPED
            if op not in _HLO_NO_WORK:
                n_instr += 1
                n_unscoped += ph is None
    return {"module": module, "phase": phase, "instructions": n_instr,
            "unscoped": n_unscoped, "scoped": n_scoped}


def _compile_phase_map(name: str, fn, sig) -> Optional[dict]:
    """Lower + compile ``fn`` from its remembered signature (a compile-
    cache read when the program ran) and parse the text.  ``None`` when
    there is nothing to read — and when the text carries no known phase,
    which is either a program that enters no scope or the stale-cache
    hazard: JAX's persistent-cache key ignores metadata, so an executable
    compiled by a build without scopes is served with its old op_names."""
    lower = getattr(fn, "lower", None)
    if lower is None or sig is None:
        return None
    try:
        lowered = lower(*sig[0], **sig[1])
        text = lowered.compile().as_text()
    except Exception as e:     # noqa: BLE001 — observability must not raise
        log.warning("phase_map(%s): cannot compile from the remembered "
                    "signature: %r", name, e)
        return None
    out = parse_hlo_phases(text or "")
    if not out["module"]:
        return None
    if not out.pop("scoped"):
        try:
            traced = lowered.as_text(debug_info=True)
        except Exception:      # noqa: BLE001
            traced = ""
        if any(f"/{s}/" in traced or f"/{s}\"" in traced
               for s in DEVICE_SCOPES):
            log.warning(
                "phase_map(%s): this build enters named scopes but the "
                "compiled text of %s carries none: the executable came "
                "from a compile cache written by a build without them "
                "(the cache key ignores metadata) — clear the cache "
                "directory once; no phase map", name, out["module"])
        else:
            log.info("phase_map(%s): %s enters no known named scope; no "
                     "phase map", name, out["module"])
        return None
    return out


def track(name: str, fn, steps_per_call: int = 1) -> TrackedFn:
    """Register ``fn`` (a jit handle) in the catalog under ``name``.
    Idempotent on an already-tracked fn (keeps the original name)."""
    if isinstance(fn, TrackedFn):
        return fn
    return TrackedFn(name, fn, steps_per_call)


# -- module globals (the registry pattern: swap via reset_for_tests) --------

_CATALOG = CostCatalog()


def get_catalog() -> CostCatalog:
    """The process-global catalog (disarmed unless configured)."""
    return _CATALOG


def phase_map(name: str) -> Optional[dict]:
    """Phase of every HLO instruction of the tracked program ``name``
    (e.g. ``"w2v_step"``): ``{"module": <hlo module name as the trace
    prints it, e.g. jit_step>, "phase": {instruction name: phase or
    "unscoped"}, "instructions": n, "unscoped": n}`` (the counts leave
    out parameters, constants, tuples and bitcasts), or ``None`` — no
    handle of that name ran while telemetry was on, it cannot be lowered,
    or its text carries no known phase (see ``_compile_phase_map``).

    Lowers and compiles once per handle: call it AFTER a measured or
    traced window, never inside one."""
    return _CATALOG.phase_map(name)


def phase_maps() -> Dict[str, dict]:
    """:func:`phase_map` of every tracked name, by HLO module name."""
    return _CATALOG.phase_maps()


def reset_for_tests() -> CostCatalog:
    global _CATALOG, _LEDGER
    _CATALOG = CostCatalog()
    _LEDGER = SetupLedger()
    return _CATALOG


def configure_costs(config, run: str = "run") -> Optional[CostCatalog]:
    """Arm the catalog from ``[obs]`` config (or ``SMTPU_COSTS=1``).

    Knobs: ``costs`` (master switch, default 0), ``costs_path`` (JSON
    artifact, default ``runs/compile_catalog.json``; empty = in-memory
    only) and ``costs_memory`` (memory_analysis compile, default 1).
    Returns the armed catalog, or None when the plane stays off."""
    g = config.get_or
    on = g("obs", "costs", 0).to_bool() or \
        os.environ.get(ENV_COSTS, "") not in ("", "0")
    if not on:
        return None
    cat = get_catalog()
    cat.enabled = True
    cat.run = run
    cat.path = g("obs", "costs_path",
                 os.path.join("runs", "compile_catalog.json")).to_string()
    cat.memory = g("obs", "costs_memory", 1).to_bool()
    return cat
