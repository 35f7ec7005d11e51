"""The chunked timing loop, the compile counter and the profiler capture.

A *chunk* is one fenced call into the family (``run_chunk(steps)``): a fixed
number of train steps followed by ``block_until_ready`` on the state.  Warm-up
is a fixed number of chunks, then chunks run until the window is spent.  The
rate of a run is the median over its chunks, so one slow chunk (a host hiccup
on a shared machine) moves nothing.
"""

from __future__ import annotations

import gc
import glob
import math
import os
import statistics
import time
import traceback
from dataclasses import dataclass, field

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts, from JAX's own monitoring events, programs lowered (every new
    program, cached or not), those XLA compiled (persistent-cache misses)
    and those read from the persistent cache, since the last ``mark()``."""

    def __init__(self):
        import jax.monitoring as mon

        self.lowered = self.compiled = self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_kw):
        if event == LOWERING:
            self.lowered += 1
        elif event == BACKEND_COMPILE:
            self.compiled += 1

    def _on_event(self, event, **_kw):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def mark(self) -> dict:
        # the backend-compile event also wraps a persistent-cache read
        out = {"lowered": self.lowered,
               "compiled": self.compiled - self.cache_hits,
               "cache_hits": self.cache_hits}
        self.lowered = self.compiled = self.cache_hits = 0
        return out


@dataclass
class Chunk:
    steps: int
    words: int
    seconds: float
    loss: float
    failed: bool = False
    raised: bool = False


@dataclass
class Window:
    chunks: list = field(default_factory=list)
    seconds: float = 0.0
    compiles: dict = field(default_factory=dict)

    @property
    def attempted(self) -> int:
        return sum(c.steps for c in self.chunks)

    @property
    def failed(self) -> int:
        return sum(c.steps for c in self.chunks if c.failed)

    def words_per_s(self):
        rates = [c.words / c.seconds for c in self.chunks if not c.failed]
        return statistics.median(rates) if rates else None


def run_chunk(family, steps: int) -> Chunk:
    """One chunk; a chunk that raises or returns a non-finite loss fails
    all its steps."""
    t0 = time.perf_counter()
    try:
        words, loss = family.run_chunk(steps)
    except Exception:             # a failed operation is counted and shown
        traceback.print_exc()
        return Chunk(steps, 0, time.perf_counter() - t0, math.nan, True, True)
    return Chunk(steps, words, time.perf_counter() - t0, loss,
                 not math.isfinite(loss))


def measure(family, steps: int, counter: CompileCounter, done) -> Window:
    """Chunks back to back until ``done(window)`` (or a chunk raises: the
    state is suspect after that); compilations inside are counted."""
    win = Window()
    counter.mark()
    t0 = time.perf_counter()
    while True:
        win.chunks.append(run_chunk(family, steps))
        win.seconds = time.perf_counter() - t0
        if win.chunks[-1].raised or done(win):
            break
    win.compiles = counter.mark()
    return win


def traced(family, steps: int, chunks: int, counter: CompileCounter,
           trace_dir: str, annotate):
    """(Window, path of the ``.xplane.pb``) of ``chunks`` chunks under the
    profiler.  The Python tracer is off: its call events are most of a
    trace's bytes and slow the host the trace is there to observe."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        # the harness's garbage (set-up's arrays, the reference's) is freed
        # at the runtime's next call: make that call here, not the window's
        # first train() (PythonRefManager::CollectGarbage, 4-8 ms of it)
        gc.collect()
        jax.block_until_ready(jax.jit(lambda x: x + 1)(0))
        with annotate("bench/window"):
            win = measure(family, steps, counter,
                          lambda w: len(w.chunks) >= chunks)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(found)}")
    return win, found[0]
