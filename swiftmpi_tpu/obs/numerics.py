"""Numerics health plane (ISSUE 13): in-jit gradient/EF/quantization
telemetry with a host-side anomaly detector.

The wire stack is deliberately lossy — int8/bf16 ``wire_quant`` with
error-feedback residuals, window staleness, hogwild races — and this
module is the runtime evidence that the gradients riding it are still
healthy.  Three pieces:

* **Traced bundle helpers** (:func:`push_stats`, :func:`state_stats`) —
  pure ``jnp`` reductions the jitted step builders fold into the
  existing fused scan when ``[obs] numerics`` is armed: gradient
  sum-of-squares split by hot/tail plane, nonfinite element counts,
  update-vs-param mass, and per-field EF residual mass.  With the plane
  off the builders never call them, so the traced program — and the
  trajectory — is bit-identical to a build without this module.

* :class:`NumericsCollector` — the host-side staging target.  Traced
  code ships the bundle out through ``jax.debug.callback`` (the traffic
  ledger discipline: no host sync on the dispatch path); the collector
  folds it into cumulative state and mirrors it as declared
  ``numerics/*`` registry series from a StepRecorder sampler.  The
  quantization-error tap (:meth:`NumericsCollector.quant_tap`) is
  handed to ``transfer.api.set_numerics_tap`` so all four backends'
  EF/quantize paths book their pre-vs-post error through one funnel.

* :class:`AnomalyDetector` — rolling EWMA+MAD baselines per series,
  emitting schema-versioned (:data:`SCHEMA`) ``numerics/anomaly``
  telemetry events with severity and evidence.  Observe-only by
  default; the Controller can register a demote hook that fires on
  SUSTAINED EF-residual runaway (``[obs] numerics_patience``
  consecutive anomalous windows) to drop ``wire_quant`` to lossless.
  Baselines serialize (:meth:`AnomalyDetector.state`) so checkpoints
  carry them across ``train_with_resume`` restarts instead of
  re-learning — and false-alarming — on the first post-restore window.

Cross-rank divergence is the fleet half: :func:`cross_rank_divergence`
scores the per-rank ``numerics/grad_norm`` gauges the FleetCollector
extracts from aligned steps.
"""

from __future__ import annotations

import json
import math
import threading
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from swiftmpi_tpu.parameter.sparse_table import hot_name, is_ef_field

#: anomaly event payload schema rides every ``numerics/anomaly`` event
SCHEMA = "smtpu-numerics/1"

#: anomaly kinds the detector emits (docs/ARCHITECTURE.md "Numerics
#: health" documents the triage story per kind)
ANOMALY_KINDS = ("loss_spike", "grad_norm_explosion",
                 "ef_residual_runaway", "nonfinite",
                 "cross_rank_divergence")

#: gauge-series -> anomaly kind scored by the rolling baseline (all
#: upward-only: a shrinking norm is convergence, not an anomaly)
_SERIES_KIND = {
    "numerics/loss": "loss_spike",
    "numerics/grad_norm": "grad_norm_explosion",
    "numerics/grad_norm_hot": "grad_norm_explosion",
    "numerics/grad_norm_tail": "grad_norm_explosion",
    "numerics/update_ratio": "grad_norm_explosion",
}


def enabled(config) -> bool:
    """The ``[obs] numerics`` master switch (default 0 = off)."""
    return config.get_or("obs", "numerics", 0).to_bool()


def detector_from_config(config) -> "AnomalyDetector":
    """Build a detector from the ``[obs] numerics_*`` knob family."""
    g = config.get_or
    return AnomalyDetector(
        alpha=g("obs", "numerics_alpha", 0.1).to_float(),
        k=g("obs", "numerics_mad_k", 6.0).to_float(),
        warmup=g("obs", "numerics_warmup", 8).to_int32(),
        patience=g("obs", "numerics_patience", 3).to_int32(),
    )


# -- traced bundle helpers --------------------------------------------------

def push_stats(slots, grads: dict, n_hot: int):
    """One step's push-gradient statistics, as traced reductions.

    ``slots`` is the per-row slot array (any shape whose elements match
    the leading dims of each grad; ``None`` for dense pushes, which are
    all-tail by definition); ``grads`` the per-field row gradients;
    ``n_hot`` the static hot-plane row count (0 = no hot plane).

    Returns ``(sq_total, sq_hot, nonfinite)``: finite-masked gradient
    sum-of-squares (total and hot-plane share) and the nonfinite element
    count.  Nonfinite elements are EXCLUDED from the norms — a single
    NaN must show up in ``numerics/nonfinite``, not poison the
    grad-norm baseline into permanent NaN.
    """
    sq_total = jnp.zeros((), jnp.float32)
    sq_hot = jnp.zeros((), jnp.float32)
    nonfin = jnp.zeros((), jnp.int32)
    for g in grads.values():
        g32 = jnp.asarray(g, jnp.float32)
        finite = jnp.isfinite(g32)
        nonfin = nonfin + jnp.sum(
            (~finite).astype(jnp.int32), dtype=jnp.int32)
        row_sq = jnp.sum(jnp.where(finite, g32, 0.0) ** 2, axis=-1)
        sq_total = sq_total + jnp.sum(row_sq)
        if n_hot > 0 and slots is not None:
            hot = ((slots >= 0) & (slots < n_hot)).astype(jnp.float32)
            sq_hot = sq_hot + jnp.sum(row_sq * hot)
    return sq_total, sq_hot, nonfin


def state_stats(before: dict, after: dict, grad_fields):
    """Once-per-dispatch table statistics, as traced reductions.

    ``before``/``after`` are the table state at dispatch entry and
    exit; ``grad_fields`` the parameter fields the step updates.
    Returns ``(upd_sq, par_sq, ef_mass, nonfin)``: finite-masked
    update and parameter sum-of-squares (their ratio is the classic
    update/param health number), per-EF-plane residual L1 mass keyed by
    the base field name, and the nonfinite element count across the
    updated params and residual planes.
    """
    upd_sq = jnp.zeros((), jnp.float32)
    par_sq = jnp.zeros((), jnp.float32)
    nonfin = jnp.zeros((), jnp.int32)
    keys = []
    for f in grad_fields:
        keys.append(f)
        if hot_name(f) in after:        # hybrid replicated hot overlay
            keys.append(hot_name(f))
    for f in keys:
        b = jnp.asarray(before[f], jnp.float32)
        a = jnp.asarray(after[f], jnp.float32)
        fin = jnp.isfinite(a)
        nonfin = nonfin + jnp.sum((~fin).astype(jnp.int32),
                                  dtype=jnp.int32)
        a0 = jnp.where(fin, a, 0.0)
        b0 = jnp.where(jnp.isfinite(b), b, 0.0)
        upd_sq = upd_sq + jnp.sum((a0 - b0) ** 2)
        par_sq = par_sq + jnp.sum(b0 ** 2)
    ef_mass = {}
    for name in after:
        if not is_ef_field(name):
            continue
        r = jnp.asarray(after[name], jnp.float32)
        fin = jnp.isfinite(r)
        nonfin = nonfin + jnp.sum((~fin).astype(jnp.int32),
                                  dtype=jnp.int32)
        base = name[:name.rindex("@")]
        ef_mass[base] = jnp.sum(jnp.abs(jnp.where(fin, r, 0.0)))
    return upd_sq, par_sq, ef_mass, nonfin


def spec_stats(pushes, n_hot: int):
    """Fold :func:`push_stats` over one step's PushSpec list (also
    accepts scan-stacked specs — the reductions are shape-agnostic)."""
    sq = jnp.zeros((), jnp.float32)
    hot = jnp.zeros((), jnp.float32)
    nf = jnp.zeros((), jnp.int32)
    for spec in pushes:
        s, h, n = push_stats(spec.slots, spec.grads, n_hot)
        sq, hot, nf = sq + s, hot + h, nf + n
    return sq, hot, nf


def tree_stats(tree):
    """Finite-masked sum-of-squares + nonfinite count over a pytree
    (the dense trainer's grads/updates/params — no slot identity, no
    hot plane)."""
    sq = jnp.zeros((), jnp.float32)
    nonfin = jnp.zeros((), jnp.int32)
    for g in jax.tree_util.tree_leaves(tree):
        g32 = jnp.asarray(g, jnp.float32)
        fin = jnp.isfinite(g32)
        nonfin = nonfin + jnp.sum((~fin).astype(jnp.int32),
                                  dtype=jnp.int32)
        sq = sq + jnp.sum(jnp.where(fin, g32, 0.0) ** 2)
    return sq, nonfin


def stage_dense(collector: "NumericsCollector", params, grads,
                updates, loss) -> None:
    """Dense-trainer bundle (models/trainer.py): grad mass,
    update/param ratio and nonfinite counts over the param pytree —
    no hot plane, no EF residuals.  ``params`` is the PRE-update
    pytree; ``loss`` the step's scalar loss."""
    gsq, g_nf = tree_stats(grads)
    upd_sq, u_nf = tree_stats(updates)
    par_sq, _ = tree_stats(params)
    bundle = {
        "gsq": gsq, "gsq_hot": jnp.zeros((), jnp.float32),
        "upd_sq": upd_sq, "par_sq": par_sq,
        "nonfinite": g_nf + u_nf,
        "loss_sum": jnp.asarray(loss, jnp.float32),
        "loss_n": jnp.ones((), jnp.float32),
    }
    collector.stage_traced(bundle, {})


def stage_step(collector: "NumericsCollector", state0, state1,
               grad_acc, es, ec, grad_fields) -> None:
    """Assemble one dispatch's bundle inside the traced step and ship
    it to ``collector``: ``grad_acc`` is the (sq, sq_hot, nonfinite)
    accumulation over the dispatch's pushes, ``state0``/``state1`` the
    table at dispatch entry/exit, ``es``/``ec`` the loss sum and
    example count the step already computes."""
    gsq, gsq_hot, g_nf = grad_acc
    upd_sq, par_sq, ef_mass, s_nf = state_stats(state0, state1,
                                                grad_fields)
    bundle = {
        "gsq": gsq, "gsq_hot": gsq_hot,
        "upd_sq": upd_sq, "par_sq": par_sq,
        "nonfinite": (jnp.asarray(g_nf, jnp.int32)
                      + jnp.asarray(s_nf, jnp.int32)),
        "loss_sum": jnp.asarray(es, jnp.float32),
        "loss_n": jnp.asarray(ec, jnp.float32),
    }
    collector.stage_traced(bundle, ef_mass)


# -- host-side collector ----------------------------------------------------

class NumericsCollector:
    """Staging target for the traced bundle + registry mirror.

    ``stage_traced`` is called from inside the jitted step with a flat
    dict of scalar reductions; the values arrive on the host through
    ``jax.debug.callback`` whenever the runtime retires the dispatch —
    asynchronously, so the dispatch path never blocks on telemetry.
    ``sampler`` runs on the StepRecorder's record path and publishes
    the bundles that arrived since it last ran (plus cumulative
    nonfinite / quant-error totals) as ``numerics/*`` series, letting
    the detector score each ONCE, in order of arrival: a loop that runs
    ahead of the device (the train loops launch one program a step and
    wait for none) finds several bundles at one record and none at the
    next, and a bundle scored again with every record it outlives would
    walk the baseline's deviation to zero, so that the next fresh value
    reads as an anomaly.  ``sync(reg)`` scores the stragglers at a safe
    point.
    """

    def __init__(self, detector: Optional["AnomalyDetector"] = None):
        self.detector = detector
        self._lock = threading.Lock()
        #: (bundle, ef_mass) of each dispatch not yet published
        self._pending: List[tuple] = []          # guarded-by: _lock
        self._nonfinite = 0.0                    # guarded-by: _lock
        self._quant_err = 0.0                    # guarded-by: _lock
        self._bundles = 0                        # guarded-by: _lock

    # .. staging (called from traced OR eager code) ........................

    def stage_traced(self, bundle: dict, ef_mass: dict) -> None:
        """Ship one dispatch's bundle out of traced code.  ``bundle``
        holds scalar tracers (gsq/gsq_hot/upd_sq/par_sq/nonfinite/
        loss_sum/loss_n), ``ef_mass`` per-field scalar tracers."""
        jax.debug.callback(self._on_bundle, bundle, ef_mass)

    def _on_bundle(self, bundle, ef_mass) -> None:
        with self._lock:
            self._pending.append(
                ({k: float(v) for k, v in bundle.items()},
                 {k: float(v) for k, v in ef_mass.items()}))
            self._nonfinite += float(bundle.get("nonfinite", 0.0))
            self._bundles += 1

    def quant_tap(self, err_sq) -> None:
        """Accumulate one quantized window's pre-vs-post error norm.
        Works traced (xla/tpu call it inside ``ef_quantize_window``)
        and eager (the local oracle's numpy path)."""
        if isinstance(err_sq, jax.core.Tracer):
            jax.debug.callback(self._on_quant, err_sq)
        else:
            self._on_quant(err_sq)

    def _on_quant(self, err_sq) -> None:
        v = float(np.asarray(err_sq))
        if not math.isfinite(v):
            with self._lock:
                self._nonfinite += 1.0
            return
        with self._lock:
            self._quant_err += math.sqrt(max(v, 0.0))

    def sync(self, reg=None) -> None:
        """Drain in-flight debug callbacks (call at safe points — end
        of train, before a final record — never per step) and, given
        the registry, publish and score the bundles they brought."""
        jax.effects_barrier()
        if reg is not None:
            self.sampler(reg)

    @property
    def bundles(self) -> int:
        """Dispatch bundles received so far (train_metrics surface)."""
        with self._lock:
            return self._bundles

    # .. publishing ........................................................

    def sampler(self, reg) -> None:
        """StepRecorder sampler: mirror each bundle that arrived since
        the last call as declared series, and let the detector score
        it — every bundle once, oldest first."""
        with self._lock:
            pending, self._pending = self._pending, []
            nonfinite = self._nonfinite
            quant_err = self._quant_err
        if not pending and not nonfinite and not quant_err:
            return
        reg.counter("numerics/nonfinite").set_total(nonfinite)
        reg.counter("numerics/quant_err").set_total(quant_err)
        for latest, ef_mass in pending:
            values: Dict[str, float] = {}
            gsq = latest.get("gsq", 0.0)
            gsq_hot = latest.get("gsq_hot", 0.0)
            values["numerics/grad_norm"] = math.sqrt(max(gsq, 0.0))
            values["numerics/grad_norm_hot"] = math.sqrt(max(gsq_hot, 0.0))
            values["numerics/grad_norm_tail"] = math.sqrt(
                max(gsq - gsq_hot, 0.0))
            par_sq = latest.get("par_sq", 0.0)
            if par_sq > 0.0:
                values["numerics/update_ratio"] = math.sqrt(
                    max(latest.get("upd_sq", 0.0), 0.0) / par_sq)
            loss_n = latest.get("loss_n", 0.0)
            if loss_n > 0.0:
                values["numerics/loss"] = \
                    latest.get("loss_sum", 0.0) / loss_n
            for name, v in values.items():
                reg.gauge(name).set(v)
            for f, m in sorted(ef_mass.items()):
                reg.gauge("numerics/ef_mass", field=f).set(m)
            if self.detector is not None:
                for f, m in sorted(ef_mass.items()):
                    values[f"numerics/ef_mass{{field={f}}}"] = m
                self.detector.on_sample(reg, values, nonfinite)
        if not pending and self.detector is not None:
            # no bundle (the eager oracle's path stages none): the
            # counter's forward motion is still the detector's to see
            self.detector.on_sample(reg, {}, nonfinite)


# -- rolling-baseline anomaly detector --------------------------------------

class AnomalyDetector:
    """EWMA+MAD baselines per series, anomaly events, demote hook.

    Per series the detector keeps ``(mean, dev, n)`` where ``dev`` is
    an EWMA of absolute deviation (a MAD proxy that needs no window
    buffer).  A sample scores anomalous when it exceeds the baseline by
    ``k`` deviations UPWARD after ``warmup`` samples; ``2k`` promotes
    the severity to ``critical``.  Anomalous samples update the
    baseline with their clamped value (``mean + k*dev``) so a genuine
    regime shift is absorbed over a few windows instead of either
    poisoning the baseline instantly or alarming forever.
    """

    def __init__(self, alpha: float = 0.1, k: float = 6.0,
                 warmup: int = 8, patience: int = 3):
        self.alpha = float(alpha)
        self.k = float(k)
        self.warmup = max(int(warmup), 1)
        self.patience = max(int(patience), 1)
        self._base: Dict[str, List[float]] = {}   # series -> [m, dev, n]
        self._streaks: Dict[str, int] = {}
        self._nonfinite_seen = 0.0
        self._hooks: List[Callable[[dict], None]] = []
        self._hook_fired = False
        self.anomalies_emitted = 0

    # .. hook contract (docs/ARCHITECTURE.md "Numerics health") ...........

    def add_demote_hook(self, fn: Callable[[dict], None]) -> None:
        """Register ``fn(anomaly)`` to fire ONCE on sustained EF-residual
        runaway (``patience`` consecutive anomalous windows on any
        ``numerics/ef_mass`` series).  Observe-only until someone calls
        this — the Controller's ``attach_numerics`` is the one caller."""
        self._hooks.append(fn)

    # .. scoring ..........................................................

    def observe(self, series: str, value: float) -> Optional[dict]:
        """Score one sample against the series' rolling baseline and
        update it.  Returns the anomaly dict (kind/severity/evidence)
        or None.  Also drives the sustained-runaway streaks and fires
        the demote hook when an ef_mass streak reaches ``patience``."""
        kind = _SERIES_KIND.get(series)
        if kind is None and series.startswith("numerics/ef_mass"):
            kind = "ef_residual_runaway"
        if kind is None:
            return None
        if not math.isfinite(value):
            return self._mk("nonfinite", series, value, None, None,
                            "critical")
        m, dev, n = self._base.get(series, (value, 0.0, 0.0))
        anomaly = None
        if n >= self.warmup:
            scale = max(dev, 1e-3 * max(abs(m), 1.0), 1e-12)
            z = (value - m) / scale
            if z > 2.0 * self.k:
                anomaly = self._mk(kind, series, value, m, dev,
                                   "critical", z=z)
            elif z > self.k:
                anomaly = self._mk(kind, series, value, m, dev,
                                   "warning", z=z)
        absorbed = value if anomaly is None else m + self.k * max(dev, 0.0)
        a = self.alpha
        m = m + a * (absorbed - m) if n else absorbed
        dev = dev + a * (abs(absorbed - m) - dev)
        self._base[series] = [m, dev, n + 1]
        if kind == "ef_residual_runaway":
            streak = self._streaks.get(series, 0) + 1 if anomaly else 0
            self._streaks[series] = streak
            if anomaly is not None and streak >= self.patience \
                    and not self._hook_fired:
                self._hook_fired = True
                anomaly["sustained"] = streak
                for h in list(self._hooks):
                    h(dict(anomaly))
        return anomaly

    def on_sample(self, reg, values: Dict[str, float],
                  nonfinite_total: float) -> List[dict]:
        """One recorded step's worth of scoring: every gauge in
        ``values`` plus the cumulative nonfinite counter (any forward
        motion is a critical anomaly — NaNs never self-heal)."""
        out = []
        if nonfinite_total > self._nonfinite_seen:
            out.append(self._mk(
                "nonfinite", "numerics/nonfinite",
                nonfinite_total - self._nonfinite_seen, None, None,
                "critical"))
            self._nonfinite_seen = nonfinite_total
        for series, v in values.items():
            a = self.observe(series, v)
            if a is not None:
                out.append(a)
        for a in out:
            self._emit(reg, a)
        return out

    def _mk(self, kind, series, value, mean, dev, severity, z=None):
        a = {"schema": SCHEMA, "anomaly": kind, "series": series,
             "severity": severity, "value": float(value)}
        if mean is not None:
            a["baseline"] = float(mean)
            a["mad"] = float(dev)
        if z is not None:
            a["z"] = float(z)
        return a

    def _emit(self, reg, anomaly: dict) -> None:
        from swiftmpi_tpu import obs
        self.anomalies_emitted += 1
        reg.counter("numerics/anomalies",
                    severity=anomaly["severity"]).inc()
        rec = obs.get_recorder()
        if rec is not None:
            rec.event("numerics/anomaly", anomaly)
        if anomaly["severity"] == "critical":
            # triggered profiler window (ISSUE 14): a critical anomaly
            # captures the very steps that misbehaved — no-op unless
            # [obs] profile_on_anomaly armed a session
            from swiftmpi_tpu.obs import profiler as obs_profiler
            from swiftmpi_tpu.obs import trace as obs_trace
            obs_profiler.on_critical_anomaly(anomaly)
            # flight-recorder dump (ISSUE 15): preserve the last-N
            # window wire records surrounding the anomaly — no-op
            # unless a tracer is installed with [obs] trace_on_anomaly
            obs_trace.on_critical_anomaly(anomaly)

    # .. checkpoint carry ..................................................

    def state(self) -> dict:
        """JSON-able rolling state for ``save_checkpoint(extra=...)``."""
        return {"schema": SCHEMA, "alpha": self.alpha, "k": self.k,
                "warmup": self.warmup, "patience": self.patience,
                "series": {s: list(v) for s, v in self._base.items()},
                "streaks": dict(self._streaks),
                "nonfinite_seen": self._nonfinite_seen,
                "hook_fired": self._hook_fired}

    def load_state(self, state: dict) -> bool:
        """Restore baselines saved by :meth:`state`.  Unknown or
        foreign-schema payloads are ignored (False) — a detector must
        never crash a resume over its own bookkeeping."""
        if not isinstance(state, dict) or \
                state.get("schema") != SCHEMA:
            return False
        self._base = {str(s): [float(v[0]), float(v[1]), float(v[2])]
                      for s, v in (state.get("series") or {}).items()}
        self._streaks = {str(s): int(v)
                         for s, v in (state.get("streaks") or {}).items()}
        self._nonfinite_seen = float(state.get("nonfinite_seen", 0.0))
        self._hook_fired = bool(state.get("hook_fired", False))
        return True

    def state_bytes(self) -> np.ndarray:
        """:meth:`state` as a uint8 array (the checkpoint ``extra``
        vehicle — npz carries arrays, not dicts)."""
        raw = json.dumps(self.state()).encode("utf-8")
        return np.frombuffer(raw, dtype=np.uint8)

    def load_state_bytes(self, arr) -> bool:
        try:
            state = json.loads(np.asarray(arr, np.uint8)
                               .tobytes().decode("utf-8"))
        except (ValueError, UnicodeDecodeError):
            return False
        return self.load_state(state)


# -- fleet half -------------------------------------------------------------

def cross_rank_divergence(per_step: Dict[int, Dict[str, float]],
                          factor: float = 4.0,
                          min_ranks: int = 2) -> List[dict]:
    """Score aligned per-rank grad norms for cross-rank divergence.

    ``per_step`` maps step -> {rank: grad_norm}.  A step where the
    max/min ratio across >= ``min_ranks`` live ranks exceeds ``factor``
    is a ``warning``; ``factor**2`` promotes to ``critical``.  Returns
    anomaly dicts (same shape the detector emits) sorted by step —
    the FleetCollector folds them into the merged timeline.
    """
    out = []
    for step in sorted(per_step):
        norms = {r: v for r, v in per_step[step].items()
                 if v is not None and math.isfinite(v)}
        if len(norms) < min_ranks:
            continue
        lo_rank = min(norms, key=lambda r: norms[r])
        hi_rank = max(norms, key=lambda r: norms[r])
        lo, hi = norms[lo_rank], norms[hi_rank]
        ratio = hi / max(lo, 1e-12)
        if ratio <= factor:
            continue
        severity = "critical" if ratio > factor * factor else "warning"
        out.append({"schema": SCHEMA, "anomaly": "cross_rank_divergence",
                    "series": "numerics/grad_norm", "severity": severity,
                    "step": int(step), "ratio": float(ratio),
                    "max_rank": str(hi_rank), "min_rank": str(lo_rank),
                    "value": float(hi), "baseline": float(lo)})
    return out
