"""Measurement-driven kernel selection (autotuning verdicts).

The round-2 on-chip profile showed XLA's HBM row gather is
transaction-bound at ~3.5% of HBM peak — but whether the Pallas
VMEM-resident alternative actually beats it is a *measurement*, not a
judgment call, and the answer may differ per platform/generation.  This
module is the tiny persistence layer that lets microbenchmarks
(scripts/gather_micro.py, scripts/scatter_micro.py) record their A/B
verdicts and lets hot paths (transfer/xla.py) consult them at trace
time:

    record("vmem_gather", "tpu", {"win": True, "pallas_ms": ..,
                                  "xla_ms": ..})
    lookup("vmem_gather", "tpu")  -> dict | None

Verdicts live in ``.bench_cache/calibration.json`` at the repo root
(or ``$SMTPU_CALIBRATION``) — a generated, gitignored file: it exists
only after a micro-benchmark recorded it on the machine at hand.
Absent the file, every gate defaults to the XLA path, so a cold
environment can never get slower.

The reference has no analogue (its hot loop is fixed C++); this is the
TPU-first replacement for hand-tuning.
"""

from __future__ import annotations

import json
import os
import sys
import threading
from typing import Optional

_LOCK = threading.Lock()
_CACHE: Optional[dict] = None
_STACK: Optional[dict] = None
_STALE_WARNED: set = set()


def _path() -> str:
    env = os.environ.get("SMTPU_CALIBRATION")
    if env:
        return env
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, ".bench_cache", "calibration.json")


def _load() -> dict:
    global _CACHE
    if _CACHE is None:
        try:
            with open(_path()) as f:
                _CACHE = json.load(f)
        except (OSError, ValueError):
            _CACHE = {}
    return _CACHE


def stack_key() -> dict:
    """The software stack a verdict was measured under: jaxlib and
    libtpu versions.  A kernel's win/loss (or even its lowerability —
    see the recorded ``taa``/``take`` Mosaic rejections) can flip
    across compiler releases, so the stack is part of a verdict's
    identity just like the device kind already in the key.  Resolved
    without initializing a JAX backend, so the ``--stale-check`` CLI
    stays cheap enough for ``run_tier1.sh``."""
    global _STACK
    if _STACK is None:
        try:
            import jaxlib
            jl = getattr(jaxlib, "__version__", "unknown")
        except Exception:
            jl = "unknown"
        lt = "none"
        try:
            from importlib import metadata
            for dist in ("libtpu", "libtpu-nightly"):
                try:
                    lt = metadata.version(dist)
                    break
                except metadata.PackageNotFoundError:
                    continue
        except Exception:
            lt = "unknown"
        _STACK = {"jaxlib": jl, "libtpu": lt}
    return dict(_STACK)


def _stale_reason(verdict: dict) -> Optional[str]:
    """Why a verdict must not steer a gate on this stack, or None."""
    got = verdict.get("stack")
    if not isinstance(got, dict):
        return "recorded without a stack stamp (pre-stamp format)"
    cur = stack_key()
    diffs = [f"{k} {got.get(k, '?')} -> {cur[k]}"
             for k in cur if got.get(k) != cur[k]]
    if diffs:
        return "recorded on a different stack: " + ", ".join(diffs)
    return None


def lookup(name: str, platform: str) -> Optional[dict]:
    """Most recent verdict for (kernel, platform), or None.

    A verdict recorded under a different jaxlib/libtpu stack (or
    before stamps existed) is rejected with a loud re-calibrate
    message: the device kind in the key already pins the chip, and the
    stamp pins the compiler — a stale A/B result must never silently
    steer a data-plane gate."""
    key = f"{name}:{platform}"
    verdict = _load().get(key)
    if verdict is None:
        return None
    reason = _stale_reason(verdict)
    if reason is not None:
        if key not in _STALE_WARNED:
            _STALE_WARNED.add(key)
            print(f"calibration: STALE verdict ignored for {key} "
                  f"({reason}) — RE-CALIBRATE via "
                  f"scripts/gather_micro.py --ab-only and "
                  f"scripts/scatter_micro.py --ab-only",
                  file=sys.stderr, flush=True)
        return None
    return verdict


def stale_keys() -> list:
    """``[(key, reason)]`` for every stored verdict this stack must
    reject — the ``run_tier1.sh`` advisory and the ``--stale-check``
    CLI read this without going through per-gate lookups."""
    out = []
    for key, verdict in sorted(_load().items()):
        if not isinstance(verdict, dict):
            continue
        reason = _stale_reason(verdict)
        if reason is not None:
            out.append((key, reason))
    return out


def record(name: str, platform: str, verdict: dict) -> None:
    """Persist a verdict stamped with the current jaxlib/libtpu stack;
    merges with the existing file under a lock."""
    global _CACHE
    verdict = dict(verdict)
    verdict.setdefault("stack", stack_key())
    with _LOCK:
        path = _path()
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
        data[f"{name}:{platform}"] = verdict
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        _CACHE = data


def clear(name: str) -> None:
    """Remove every recorded verdict for ``name`` (all device kinds) —
    the rollback path when a kernel that won its microbench A/B then
    breaks the full step (the gate must fail open to the XLA path)."""
    global _CACHE
    with _LOCK:
        path = _path()
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            # match the on-disk state just observed: the in-process
            # memo must not keep serving a verdict the caller believes
            # was cleared
            _CACHE = {}
            return
        kept = {k: v for k, v in data.items()
                if not k.startswith(name + ":")}
        if kept != data:
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(kept, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        _CACHE = kept


def reset_cache() -> None:
    """Drop the in-process memo (tests; or after an external write)."""
    global _CACHE
    _CACHE = None
    _STALE_WARNED.clear()


def device_key() -> str:
    """Calibration key for the current accelerator: the device *kind*
    (e.g. ``TPU v5 lite``), not the bare platform — a win measured on
    one TPU generation must not gate the kernel on another."""
    import jax

    return jax.devices()[0].device_kind


def on_tpu() -> bool:
    """Is the default device a TPU?  Checked via the DEVICE platform,
    not ``jax.default_backend()`` (a PJRT plug-in may register under its
    own backend name while its devices report platform ``tpu``).  A
    backend that fails to initialise raises here: answering False would
    put every Pallas kernel in interpret mode on a TPU host."""
    import jax

    return jax.devices()[0].platform == "tpu"


def ab_verdict(name: str, xla_ms: float, pallas_ms: float = None,
               correct: bool = None, shape: str = None,
               error: str = None, extra: dict = None) -> dict:
    """Build the standard A/B verdict (shared by the gather and scatter
    microbench harnesses) and record it when running on a real chip:
    a win requires the kernel to be CORRECT on-device and >=10% faster
    than the XLA path; any lowering failure is a loud non-win.
    ``extra`` merges additional keys (e.g. the winning kernel variant)
    into the recorded verdict."""
    if error is not None:
        verdict = {"win": False, "error": error,
                   "xla_ms": round(xla_ms, 3)}
    else:
        verdict = {"win": bool(correct and pallas_ms < 0.9 * xla_ms),
                   "correct": bool(correct),
                   "pallas_ms": round(pallas_ms, 3),
                   "xla_ms": round(xla_ms, 3)}
        if shape:
            verdict["shape"] = shape
    verdict.update(extra or {})
    import jax

    if os.environ.get("SMTPU_AB_RECORD", "1") == "0":
        # rollback mode: measure and print, but never re-arm a verdict
        # diagnosed as breaking the full step
        print(f"calibration NOT recorded (SMTPU_AB_RECORD=0): "
              f"{name} -> {verdict}", flush=True)
        return verdict
    if jax.devices()[0].platform == "tpu":
        key = device_key()
        record(name, key, verdict)
        print(f"calibration recorded: {name}:{key} -> {verdict}",
              flush=True)
    return verdict


# every Pallas kernel behind a measurement gate; pallas_status walks
# this list so a new kernel cannot silently count as validated
_PALLAS_KERNELS = ("vmem_gather", "vmem_scatter", "replica_scatter",
                   "ring_push")

#: pseudo device-kind for interpret-mode (off-chip) oracle runs — a
#: correctness exercise, never a performance verdict
INTERPRET_KIND = "interpret"


def record_interpret(name: str, correct: bool, shape: str = None,
                     extra: dict = None) -> dict:
    """Record an interpret-mode numpy-oracle exercise for a kernel.

    This is the off-chip half of the validation story: it proves the
    kernel's *semantics* (against a host oracle, interpret=True) without
    touching a chip, so ``pallas_status`` can distinguish "never
    exercised" from "exercised off-chip, awaiting on-chip A/B".  It
    carries no timing and can never flip a ``gated()`` decision — the
    gate only consults the real device kind."""
    verdict = {"correct": bool(correct), "interpret": True}
    if shape:
        verdict["shape"] = shape
    verdict.update(extra or {})
    record(name, INTERPRET_KIND, verdict)
    return verdict


def pallas_status(kind: Optional[str] = None) -> str:
    """One-line Pallas validation status for a device kind (r5 verdict
    Next #6): the kernels count as a hardware capability ONLY once a
    measured on-chip A/B verdict (pallas_ms vs xla_ms) exists for the
    key — until then bench/calibration output must carry the explicit
    ``unvalidated-on-tpu`` marker instead of implying the capability.
    A recorded lowering *error* is an attempt, not a validation, and an
    interpret-mode oracle pass (``record_interpret``) upgrades the
    marker to "exercised off-chip" without clearing it."""
    if kind is None:
        kind = device_key()
    verdicts = {n: lookup(n, kind) for n in _PALLAS_KERNELS}
    measured = {n: v for n, v in verdicts.items()
                if v and "pallas_ms" in v and "xla_ms" in v}
    if not measured:
        errs = sorted(n for n, v in verdicts.items() if v and "error" in v)
        if errs:
            return ("unvalidated-on-tpu (attempted, lowering failed: "
                    + ", ".join(errs) + ")")
        interp = sorted(
            n for n in _PALLAS_KERNELS
            if (lookup(n, INTERPRET_KIND) or {}).get("correct"))
        if interp:
            return ("unvalidated-on-tpu (exercised off-chip, "
                    "interpret-mode correct: " + ", ".join(interp) + ")")
        return "unvalidated-on-tpu"
    wins = sorted(n for n, v in measured.items() if v.get("win"))
    if wins:
        return "validated: win (" + ", ".join(wins) + ")"
    return "validated: no-win"


def gated(name: str, env_var: str, fits: bool,
          manual: bool = False) -> bool:
    """The shared measurement-driven gate policy (one copy for all
    Pallas kernels): env force-off beats everything; a kernel that
    doesn't fit never routes; env force-on is the caller's explicit
    override (tests/experiments); auto requires TPU backend, a single
    device (the kernels are single-core VMEM programs — sharded
    operands would be re-laid-out or rejected by the partitioner), and
    a recorded on-chip win for this device kind.

    ``manual=True`` relaxes the single-device requirement: the caller
    is inside ``shard_map`` where operands are already per-device local
    arrays, so the partitioner hazard doesn't exist and the single-chip
    verdict is the right proxy for each core's kernel."""
    import jax

    mode = os.environ.get(env_var, "auto").lower()
    if mode in ("0", "off", "false"):
        return False
    if not fits:
        return False
    if mode in ("1", "on", "true"):
        return True
    if not on_tpu():
        return False
    if not manual and jax.device_count() != 1:
        return False
    verdict = lookup(name, device_key())
    return bool(verdict and verdict.get("win"))


#: legal values of the ``[cluster] data_plane:`` knob
DATA_PLANE_MODES = ("auto", "pallas", "xla")


def data_plane_gated(mode: str, name: str, env_var: str, fits: bool,
                     manual: bool = False) -> bool:
    """Resolve the ``[cluster] data_plane:`` knob for one kernel.

    The per-process env var stays the strongest signal (it is the
    experiment/test override, exactly as for the other gates); below
    it, ``xla`` pins the knob off, ``pallas`` forces the kernel on for
    any shape that fits (an explicit operator decision — no verdict
    required), and ``auto`` defers to the measured-verdict policy in
    :func:`gated`, so absent a recorded on-chip win the XLA path
    stays."""
    if mode not in DATA_PLANE_MODES:
        raise ValueError(
            f"[cluster] data_plane must be one of {DATA_PLANE_MODES}, "
            f"got {mode!r}")
    if os.environ.get(env_var) is not None:
        return gated(name, env_var, fits, manual=manual)
    if mode == "xla":
        return False
    if mode == "pallas":
        return bool(fits)
    return gated(name, env_var, fits, manual=manual)


def main(argv=None) -> int:
    """``python -m swiftmpi_tpu.ops.calibration --stale-check``: print
    an advisory staleness report for the verdict file; exits 0 so
    run_tier1.sh prints this next to the pytest verdict without ever
    changing it.  ``--stale-check=strict`` promotes the report to a hard
    gate (exit 1 on any stale verdict): a serving deployment preflights
    with it to refuse to start on another stack's verdicts rather than
    silently fall back to the uncalibrated path under live traffic."""
    argv = list(sys.argv[1:] if argv is None else argv)
    strict = "--stale-check=strict" in argv
    path = _path()
    if not os.path.exists(path):
        print(f"calibration: no verdict file at {path}")
        return 0
    stale = stale_keys()
    total = len([v for v in _load().values() if isinstance(v, dict)])
    if not stale:
        print(f"calibration: {total} verdict(s) at {path} match the "
              f"current stack {stack_key()}")
        return 0
    label = "GATE" if strict else "ADVISORY"
    print(f"calibration {label}: {len(stale)}/{total} verdict(s) at "
          f"{path} are STALE on this stack {stack_key()} — gates fall "
          f"back to the XLA path; re-calibrate on-chip via "
          f"scripts/gather_micro.py --ab-only and "
          f"scripts/scatter_micro.py --ab-only:")
    for key, reason in stale:
        print(f"  {key}: {reason}")
    return 1 if strict else 0


if __name__ == "__main__":
    sys.exit(main())
