"""Profiling hooks (the subsystem the reference lacks — SURVEY.md §5
"Tracing/profiling: No", just an unused Timer).

Thin wrappers over ``jax.profiler``: ``trace(logdir)`` captures a
TensorBoard-loadable device trace around a code block; ``annotate(name)``
labels host spans so steps show up named in the trace; ``StepTimer``
measures steady-state step latency with device sync.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, List, Optional

import jax


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture a device/host profile into ``logdir`` (view in TensorBoard
    or xprof)."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, **attrs):
    """Named span context (TraceAnnotation) for host-side phases;
    ``attrs`` become the trace event's arguments."""
    return jax.profiler.TraceAnnotation(name, **attrs)


class StepTimer:
    """Wall-clock per-step stats with an explicit device barrier.

    Keeps every sample, so percentiles are exact order statistics,
    not bucket interpolations."""

    def __init__(self):
        self._times: List[float] = []
        self._t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, *sync_on) -> float:
        if sync_on:
            jax.block_until_ready(sync_on)
        dt = time.perf_counter() - self._t0
        self._times.append(dt)
        return dt

    def __len__(self) -> int:
        return len(self._times)

    @property
    def mean(self) -> float:
        return sum(self._times) / len(self._times) if self._times else 0.0

    def percentile(self, q: float) -> float:
        """Exact order-statistic percentile (``q`` in [0, 1]), linear
        interpolation between adjacent samples — numpy's default rule,
        without pulling in an array round-trip per call."""
        if not self._times:
            return 0.0
        xs = sorted(self._times)
        if len(xs) == 1:
            return xs[0]
        pos = q * (len(xs) - 1)
        lo = int(pos)
        hi = min(lo + 1, len(xs) - 1)
        return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)

    @property
    def p50(self) -> float:
        return self.percentile(0.50)
