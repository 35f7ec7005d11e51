#!/usr/bin/env python3
"""One expert layer's forward + backward through ``moe.expert_layer``, with
the held picks fixed and the rows of the walk's LAST chunk varied.

    python scripts/expert_walk_micro.py [--tree DIR] [--cases a,b] [--calls 8]
    JAX_PLATFORMS=cpu python scripts/expert_walk_micro.py --rehearse-cpu

``parallel/moe.py::_walk`` walks the sorted picks in whole chunks of
``ROW_CHUNK`` rows and the rows that are left at the smallest rung of
``moe._rungs`` that holds them.  This script asks what a rung is worth: for
each case — an LM cell's expert widths, picks a layer and mean held picks —
it routes exactly ``held`` picks to the held experts (a router built for
it, ``_forced``), and times ``value_and_grad`` of the layer with
``moe._rungs`` replaced by each ladder tried: ``(X, chunk)`` for every ``X``
of ``TAILS`` that holds the rows left (the last chunk computed at ``X``
rows, the premise: does the time fall with the rows?), then the ladders of
``LADDERS`` — halves (the tree's own), quarter / half / whole, quarters
and eighths of the chunk (what the ``switch`` over several rungs costs
beside the one rung it takes).  ``--widths``
repeats the one-rung runs at other expert widths (the 1,856-wide expert of
``nemotron3n`` against 1,792 and 1,920: whole 128-lane tiles).  A tree
without ``_rungs`` (``--tree``: the parent's, unpacked under a directory
``.gitignore`` lists) is timed as it stands, one row a case.

A run is ``--calls`` fenced calls after a warm-up (``ms_median``), then two
traced calls reduced to self time by device op (``benchmark/lib/xplane.py``:
``busy_ms``, ``ragged_dot_ms``, the first ops).  One JSON line a run; all in
``chiprun_out/expert_walk_micro[.<tag>].json``.  ``--rehearse-cpu`` walks
the same path at toy widths and prints no time.

Cases (tokens, k of E experts, held, d_model -> d_expert, form, held picks):
``nemotron3n`` 8,192, 6 of 128, 8, 2,688 -> 1,856 ungated squared-ReLU,
3,300; ``sdar`` 16,384, 8 of 128, 16, 2,048 -> 768 SwiGLU, 9,200; ``lfm2``
32,768, 4 of 32, 8, 2,048 -> 1,792 SwiGLU, 33,800.  ``name@N`` routes ``N``
held picks instead (``nemotron3n@7000``: a last chunk that needs the top
rung, for the ``switch`` against the parent's plain loop).
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import statistics
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CASES = {   # name: (T, k, E, held, d_model, d_expert, gated, act, router, picks)
    "nemotron3n": (8192, 6, 128, 8, 2688, 1856, False, "relu2",
                   "sigmoid_bias", 3300),
    "sdar": (16384, 8, 128, 16, 2048, 768, True, "relu", "softmax", 9200),
    "lfm2": (32768, 4, 32, 8, 2048, 1792, True, "relu", "sigmoid_bias",
             33800),
}
#: rows of the last chunk tried alone, as parts of a chunk of 8
TAILS = (1, 2, 3, 4, 6, 8)
#: the ladders tried whole, as parts of a chunk
LADDERS = {"halves": (4, 8), "quarter_half": (2, 4, 8),
           "quarters": (2, 4, 6, 8), "eighths": (1, 2, 3, 4, 5, 6, 7, 8)}
#: how far the forced logits stand from the router's own (N(0, 1))
FORCE = 30.0


def _forced(key, T, d, E, n_held, picks, moe, d_ff, gated):
    """Parameters and tokens whose routing is known: token ``t`` picks
    ``picks // T`` (+ 1 for the first ``picks % T``) held experts, ``(t +
    i) % n_held`` the ``i``-th, and fills its ``k`` with experts that are
    not held.  Feature 0 is 1 and weighs ``-FORCE`` on every held expert;
    feature ``1 + j`` flags held expert ``j`` at ``+3 FORCE``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    kp, kx = jax.random.split(key)
    p = moe.init_moe_params(kp, d, d_ff, E, held=n_held, gated=gated)
    per = np.full(T, picks // T) + (np.arange(T) < picks % T)
    assert per.max() <= n_held, "more held picks a token than held experts"
    flags = np.zeros((T, n_held), np.float32)
    for i in range(int(per.max())):
        rows = np.nonzero(per > i)[0]
        flags[rows, (rows + i) % n_held] = 1.0
    x = jax.random.normal(kx, (T, d), jnp.float32)
    x = x.at[:, 0].set(1.0).at[:, 1:1 + n_held].set(flags)
    router = p.router.at[:1 + n_held].set(0.0)
    router = router.at[0, :n_held].set(-FORCE)        # held = experts 0..
    router = router.at[1 + np.arange(n_held), np.arange(n_held)].set(
        3 * FORCE)
    return p._replace(router=router), x


def _trace_ms(fn, args, calls=2) -> dict:
    import jax

    from benchmark.lib import xplane
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(calls):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        trace = xplane.load(glob.glob(
            os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0])
    d = trace.devices[0]
    window = (min(e[0] for e in d.ops), max(e[1] for e in d.ops))
    ops = xplane.op_seconds(d, window, xplane.op_group)
    return {"busy_ms": xplane.busy_seconds(d, window) * 1e3 / calls,
            "ragged_dot_ms": xplane.matching_seconds(
                d, window, "^ragged-dot")[0] * 1e3 / calls,
            "ops_ms": [[n, s * 1e3 / calls] for n, s in xplane.top(ops, 8)]}


def _ladders(moe, left: int, chunk: int, whole: bool) -> dict:
    """``{label: rungs}`` to time: one rung below the chunk for every tail
    of ``TAILS`` that holds the ``left`` rows, then (``whole``) the ladders
    of ``LADDERS``; a tree without ``_rungs`` is timed as it stands."""
    if not hasattr(moe, "_rungs"):
        return {"as_it_stands": None}
    # (X, chunk): the rest at X rows; the top rung is the loop's own body
    tried = {str(x * chunk // 8): tuple(sorted({x * chunk // 8, chunk}))
             for x in TAILS if left and x * chunk // 8 >= left}
    if whole:
        tried.update({n: tuple(x * chunk // 8 for x in parts)
                      for n, parts in LADDERS.items()})
    return tried


def _timed(step, args, calls: int) -> dict:
    import jax

    ms = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(step(*args))
        ms.append((time.perf_counter() - t0) * 1e3)
    return {"calls": calls, "ms_median": statistics.median(ms),
            "ms_min": min(ms), "ms_max": max(ms), **_trace_ms(step, args)}


def measure(names, widths, calls, out, rehearse):
    import jax
    import jax.numpy as jnp

    moe = importlib.import_module("swiftmpi_tpu.parallel.moe")
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not rehearse:
        raise SystemExit(f"no TPU here ({dev.platform}): a time comes only "
                         "from the chip; --rehearse-cpu walks the path")
    # a rehearsal walks the control flow at toy size
    shrink, narrow = (64, 16) if rehearse else (1, 1)
    chunk = moe.ROW_CHUNK // shrink
    tree = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(moe.__file__))))
    rows = []
    for name in names:
        base, _, forced = name.partition("@")
        T, k, E, n_held, d, f, gated, act, router, picks = CASES[base]
        picks = int(forced or picks) // shrink
        T, d = T // shrink, d // narrow

        def loss(p, x, cot):
            y, _aux, st = moe.expert_layer(
                p, x, k=k, router=router, held=(0, n_held),
                compute_dtype=jnp.bfloat16, row_chunk=chunk, act=act)
            return (y * cot).sum(), st

        for width in [f] + [w for w in widths if base == "nemotron3n"]:
            p, x = _forced(jax.random.key(53), T, d, E, n_held, picks, moe,
                           width // narrow, gated)
            args = (p, x, jax.random.normal(jax.random.key(54), (T, d)))
            for label, ladder in _ladders(moe, picks % chunk, chunk,
                                          width == f).items():
                if ladder is not None:
                    moe._rungs = lambda rows, ladder=ladder: ladder
                # a new jit a ladder: the walk is traced under this one
                step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                                  has_aux=True))
                (_l, st), _g = jax.block_until_ready(step(*args))
                assert float(st.held) == picks, (float(st.held), picks)
                assert float(st.dropped) == 0.0
                row = {"case": name, "d_expert": width // narrow,
                       "ladder": label, "rungs": ladder, "held": picks,
                       "walked": float(getattr(st, "walked", -1.0)),
                       "tree": tree}
                if rehearse:
                    row["rehearse_cpu"] = True
                else:
                    row.update(device=dev.device_kind,
                               **_timed(step, args, calls))
                print(json.dumps(row), flush=True)
                rows.append(row)
            del p, x, args
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as fh:
        json.dump(rows, fh, indent=1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=REPO)
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--widths", default="1792,1920",
                    help="other expert widths for nemotron3n's one-rung runs")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--tag", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, REPO)                      # benchmark.lib.xplane
    sys.path.insert(0, os.path.abspath(args.tree))
    measure(args.cases.split(","),
            [int(w) for w in args.widths.split(",") if w], args.calls,
            os.path.join(REPO, "chiprun_out", "expert_walk_micro"
                         + (f".{args.tag}" if args.tag else "") + ".json"),
            args.rehearse_cpu)
    return 0


if __name__ == "__main__":
    sys.exit(main())
