"""Compiled-program catalog: XLA cost/memory attribution + retrace
tracking (ISSUE 14).

Every jitted step/kernel the model builders and transfer backends
produce funnels through :func:`track`, which wraps the jit in a
:class:`TrackedFn`.  Disarmed (the default), the wrapper is a single
attribute check around the call — the jit object, its dispatch path and
its traced program are untouched, so a default-off run is bit-identical
to one built before this module existed.  Armed (``[obs] costs: 1`` or
``SMTPU_COSTS=1``), every *compile* event — detected as growth of the
jit's own trace cache — is recorded three ways:

* ``compile/compiles{fn=}`` / ``compile/compile_ms{fn=}`` /
  ``compile/retraces{fn=}`` counters in the telemetry registry, so a
  retrace storm shows up in the JSONL stream and the budget gate, not
  just in ``tests/test_retrace_guard.py``;
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
FIRST compile is expected; only a handle compiling *again* — genuine
shape/dtype churn on one program — books a retrace.  A control-plane
safe-point recompile builds fresh handles, so it books compiles, never
retraces.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

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
                   handle_compiles: int, steps_per_call: int = 1) -> None:
        """Book one compile of ``fn`` (the unwrapped jit) under ``name``.
        ``handle_compiles`` is the wrapping handle's own compile count —
        > 1 means this very program re-traced, which is the retrace
        signal.  ``dt_ms`` is the wall time of the compiling call (it
        includes the first execution — the operator-facing number is
        "how long did the step stall for this compile")."""
        retrace = handle_compiles > 1
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


class TrackedFn:
    """The funnel wrapper around one jit handle.

    Call path invariant: the wrapped jit is ALWAYS the callee — armed
    or not, cached or first call — so arming cannot change dispatch
    behavior, only observe it.  Compile detection is the jit's own
    ``_cache_size()`` growing across a call (the same signal
    tests/test_retrace_guard.py pins); handles without a cache probe
    (plain callables) simply never book events.

    Unknown attributes forward to the wrapped fn, so ``lower()`` /
    ``_cache_size()`` callers don't need to know about the wrapper.
    """

    __slots__ = ("_fn", "name", "steps_per_call", "_compiles", "_sig",
                 "_phase_map", "__weakref__")

    def __init__(self, name: str, fn, steps_per_call: int = 1):
        self._fn = fn
        self.name = name
        self.steps_per_call = max(int(steps_per_call), 1)
        self._compiles = 0
        self._sig = None          # (args, kwargs) of ShapeDtypeStructs
        self._phase_map = _NOT_COMPUTED

    def __call__(self, *args, **kwargs):
        cat = _CATALOG
        if cat.idle:
            return self._fn(*args, **kwargs)
        if self._sig is None:
            cat.remember(self, args, kwargs)
        if not cat.enabled:
            return self._fn(*args, **kwargs)
        before = self._cache_size()
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        if before >= 0 and self._cache_size() > before:
            dt_ms = (time.perf_counter() - t0) * 1e3
            self._compiles += 1
            cat.on_compile(self.name, self._fn, args, kwargs, dt_ms,
                           self._compiles, self.steps_per_call)
        return out

    def _cache_size(self) -> int:
        cs = getattr(self._fn, "_cache_size", None)
        if cs is None:
            return -1
        try:
            return int(cs())
        except Exception:
            return -1

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
    global _CATALOG
    _CATALOG = CostCatalog()
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
