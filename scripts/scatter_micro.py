"""Microbench: scatter-add / sampling throughput on the live chip, plus
the Pallas VMEM-scatter A/B that records the calibration verdict gating
the push path (transfer/xla.py via ops/pallas_scatter.py).

Run (on the chip): python scripts/scatter_micro.py
A/B only:     ... scatter_micro.py --ab-only      (fast: the verdict
              cells alone)
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


class _NullTelemetry:
    def cell(self, *a, **k):
        pass

    def close(self):
        pass


#: ``--telemetry PATH`` swaps in obs.micro.MicroTelemetry so the cells
#: land as schema-versioned JSONL (smtpu-telemetry/1) that
#: telemetry_report.py / check_traffic_budget.py can diff like any
#: other run; default is print-only, zero overhead
MT = _NullTelemetry()


def _init_telemetry(argv, run="scatter_micro"):
    global MT
    if "--telemetry" in argv:
        path = argv[argv.index("--telemetry") + 1]
        from swiftmpi_tpu.obs.micro import MicroTelemetry
        MT = MicroTelemetry(path, run=run,
                            meta={"device": str(jax.devices()[0])})
        print(f"telemetry -> {path}", flush=True)


def timeit(fn, *a, reps=16):
    out = fn(*a)
    float(np.asarray(jax.tree_util.tree_leaves(out)[0]).reshape(-1)[0])
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*a)
    float(np.asarray(jax.tree_util.tree_leaves(out)[0]).reshape(-1)[0])
    return (time.perf_counter() - t0) / reps * 1e3


rng = np.random.default_rng(0)
capw, Nw, d = 17314, 344064, 100
gi = jnp.asarray(rng.integers(0, capw, Nw), jnp.int32)
# fused [grads|count] layout (the mean=True dense-push shape) built
# directly — no exploratory-only (Nw, d) intermediate on the window-
# critical --ab-only path
_g1_np = rng.standard_normal((Nw, d + 1)).astype(np.float32)
_g1_np[:, d] = 1.0
g1 = jnp.asarray(_g1_np)
del _g1_np
fscat = jax.jit(lambda i, g: jnp.zeros((capw, d + 1), jnp.float32)
                .at[i].add(g).sum())


def replica_scatter(i, g, lane, R):
    """The replica-spread formulation both the exploratory cell and the
    verdict-recording A/B measure — one copy so tuning it (e.g. lane
    hashing) can't make the exploratory numbers drift from the gate."""
    return jnp.zeros((R, capw, d + 1), jnp.float32).at[lane, i].add(
        g).sum(axis=0)


def replica_lanes(R):
    return jnp.asarray(np.arange(Nw) % R, jnp.int32)


def exploratory_cells():
    N = 114688          # LR bench: 8192 rows x 14 nnz
    g = jnp.asarray(rng.standard_normal((N, 1)), jnp.float32)
    gw = g1[:, :d]      # (Nw, d) grads view for the plain-scatter cell
    for cap in (512, 65536):
        idx = jnp.asarray(rng.integers(0, min(cap, 124), N), jnp.int32)
        scat = jax.jit(lambda i, g, cap=cap:
                       jnp.zeros((cap, 1), jnp.float32).at[i].add(g).sum())
        print(f"cap={cap:6d} scatter : {timeit(scat, idx, g):7.2f} ms",
              flush=True)
        if cap <= 4096:
            def oh(i, g, cap=cap):
                o = jax.nn.one_hot(i, cap, dtype=jnp.float32)  # (N, cap)
                return (o.T @ g).sum()
            print(f"cap={cap:6d} onehot  : {timeit(jax.jit(oh), idx, g):7.2f} ms",
                  flush=True)
    scat2 = jax.jit(lambda i, g: jnp.zeros((capw, d), jnp.float32)
                    .at[i].add(g).sum())
    print(f"w2v dense scatter (344K x 100 -> 17314): "
          f"{timeit(scat2, gi, gw):7.2f} ms", flush=True)
    cnt = jax.jit(lambda i: jnp.zeros((capw,), jnp.float32)
                  .at[i].add(1.0).sum())
    print(f"w2v counts scatter (344K scalars)      : "
          f"{timeit(cnt, gi):7.2f} ms", flush=True)
    print(f"w2v fused grads+count scatter (x101)   : "
          f"{timeit(fscat, gi, g1):7.2f} ms", flush=True)
    # replica-spread scatter: with ~20x slot duplication the RMW chains
    # serialize; spreading colliding rows over R replica tables (then
    # one dense reduce) shortens the chains R-fold at the cost of R x
    # table memory + a streaming sum.  If the 7ms fused scatter is
    # collision-serialization-bound this wins; if it's RMW-transaction-
    # bound it won't move.  (Round-3: scatter is now ~60% of the step.)
    for R in (4, 8, 16):
        fn = jax.jit(lambda i, g, l, R=R: replica_scatter(i, g, l, R).sum())
        print(f"w2v replica-{R} scatter (x101)          : "
              f"{timeit(fn, gi, g1, replica_lanes(R)):7.2f} ms", flush=True)
    # bf16 payload: half the scatter write bytes (RMW read stays fp32
    # accumulate? no — whole table bf16) — tells transaction- vs
    # byte-bound apart on the write side
    g1h = g1.astype(jnp.bfloat16)
    fscat16 = jax.jit(lambda i, g: jnp.zeros((capw, d + 1), jnp.bfloat16)
                      .at[i].add(g).sum())
    print(f"w2v fused scatter bf16 (x101)          : "
          f"{timeit(fscat16, gi, g1h):7.2f} ms", flush=True)
    # pre-dedup via 16-bit sort: keys < 2^15, values carried as the
    # PERMUTATION (argsort) — jnp.argsort of int32 was the 16ms cost;
    # sort_key_val on (key, iota) may beat it
    def sortseg(i, g):
        si, order = jax.lax.sort_key_val(i, jnp.arange(Nw, dtype=jnp.int32))
        sg = g[order]
        return jnp.zeros((capw, d + 1), jnp.float32).at[si].add(
            sg, indices_are_sorted=True).sum()
    print(f"w2v sorted scatter (sort_key_val)      : "
          f"{timeit(jax.jit(sortseg), gi, g1):7.2f} ms", flush=True)
    # alias sampling cost at bench shape: 2 scalar gathers per draw from
    # the 30K-entry alias arrays — a hidden transaction cost?
    from swiftmpi_tpu.ops.sampling import build_unigram_alias, sample_alias
    counts = rng.zipf(1.5, 30000).astype(np.int64)
    prob, alias = build_unigram_alias(counts)
    prob_d, alias_d = jnp.asarray(prob), jnp.asarray(alias)
    samp = jax.jit(lambda k: sample_alias(k, prob_d, alias_d,
                                          (16384, 20)).sum())
    print(f"alias sampling (16384 x 20 draws)      : "
          f"{timeit(samp, jax.random.key(0)):7.2f} ms", flush=True)


def replica_ab():
    """Replica-spread scatter A/B at the w2v fused grads+count shape —
    records the ``replica_scatter`` verdict gating transfer/xla.py's
    push (see _push_dense._scatter).  Correctness checked per R before
    timing; a loss records win=False and the gate stays closed."""
    from swiftmpi_tpu.ops import calibration

    print(f"replica A/B device: {jax.devices()[0]}", flush=True)
    xla_ms = timeit(fscat, gi, g1)
    print(f"xla fused scatter (x101 -> 17314)      : {xla_ms:7.2f} ms",
          flush=True)
    MT.cell("xla_scatter/cap17314_w101_fp32", xla_ms)
    nchk = 16384
    want = np.asarray(jnp.zeros((capw, d + 1), jnp.float32)
                      .at[gi[:nchk]].add(g1[:nchk]))
    cells = {}
    for R in (4, 8, 16):
        lane = replica_lanes(R)
        got = np.asarray(jax.jit(
            lambda i, g, l, R=R: replica_scatter(i, g, l, R))(
            gi[:nchk], g1[:nchk], lane[:nchk]))
        ok = bool(np.allclose(got, want, rtol=1e-5, atol=1e-5))
        ms = timeit(jax.jit(lambda i, g, l, R=R:
                            replica_scatter(i, g, l, R).sum()),
                    gi, g1, lane)
        print(f"replica-{R} scatter: {ms:7.2f} ms  correct={ok}",
              flush=True)
        MT.cell(f"replica_scatter/R{R}", ms, correct=float(ok))
        if ok:
            cells[R] = ms
    if cells:
        best = min(cells, key=cells.get)
        calibration.ab_verdict("replica_scatter", xla_ms, cells[best],
                               correct=True,
                               shape=f"cap={capw} w={d+1} fp32 N={Nw}",
                               extra={"R": best, "cells": {
                                   str(r): round(m, 3)
                                   for r, m in cells.items()}})
    else:
        calibration.ab_verdict("replica_scatter", xla_ms,
                               error="no correct replica cell")


def pallas_ab():
    """Pallas VMEM-resident scatter A/B at the w2v fused grads+count
    shape — records the verdict that gates the push path."""
    from swiftmpi_tpu.ops import calibration
    from swiftmpi_tpu.ops.pallas_scatter import fits_vmem, vmem_scatter_add

    print(f"A/B device: {jax.devices()[0]}", flush=True)
    xla_ms = timeit(fscat, gi, g1)
    print(f"xla fused scatter (x101 -> 17314)      : {xla_ms:7.2f} ms",
          flush=True)
    MT.cell("xla_scatter/cap17314_w101_fp32", xla_ms)
    if not fits_vmem(capw, d + 1):
        return
    try:
        # correctness first (duplicate-heavy small case), then timing
        si, sg = gi[:8192], g1[:8192]
        got = np.asarray(vmem_scatter_add(si, sg, capw))
        want = np.asarray(jnp.zeros((capw + 1, d + 1), jnp.float32)
                          .at[si].add(sg))
        correct = bool(np.allclose(got, want, rtol=1e-5, atol=1e-5))
        pscat = jax.jit(lambda i, g: vmem_scatter_add(i, g, capw).sum())
        p_ms = timeit(pscat, gi, g1)
        print(f"pallas vmem scatter (x101 -> 17314+1)  : {p_ms:7.2f} ms"
              f"  correct={correct}", flush=True)
        MT.cell("pallas_scatter/cap17314_w101_fp32", p_ms,
                correct=float(correct))
        calibration.ab_verdict("vmem_scatter", xla_ms, p_ms, correct,
                               shape=f"cap={capw} w={d+1} fp32 N={Nw}")
    except Exception as e:
        print(f"pallas vmem scatter: UNSUPPORTED ({type(e).__name__}: "
              f"{str(e)[:200]})", flush=True)
        calibration.ab_verdict("vmem_scatter", xla_ms,
                               error=f"{type(e).__name__}: {str(e)[:200]}")


def ring_ab(C=4096, width=101):
    """DMA ring exchange (ops/pallas_ring.py) vs ``lax.all_to_all`` at
    the push bucket shape — records the ``ring_push`` verdict that
    resolves the ``[cluster] data_plane:`` knob for TpuTransfer's wire
    exchange.  Needs a multi-device mesh to measure anything real: on a
    single chip the ring degenerates and only a warning is printed; off
    the chip the kernel runs its interpret-mode discharge path and the
    parity result is recorded via ``record_interpret``."""
    from swiftmpi_tpu.ops import calibration
    from swiftmpi_tpu.ops.pallas_ring import ring_exchange
    from jax.sharding import Mesh, PartitionSpec as P

    devices = jax.devices()
    n = len(devices)
    on_tpu = calibration.on_tpu()
    if on_tpu and n < 2:
        print("ring A/B: needs a multi-chip mesh (1 device visible) — "
              "no verdict recorded", flush=True)
        return
    mesh = Mesh(np.asarray(devices), ("x",))
    shape = f"n={n} C={C} w={width} fp32"
    print(f"ring A/B device: {devices[0]}  ({shape})", flush=True)
    # per-device view is (n, C, width): n bucket blocks bound for the n
    # shards — the exact operand TpuTransfer hands its wire exchange
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (n, n, C, width)), jnp.float32)

    def run(exchange):
        f = jax.shard_map(
            exchange, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
            check_vma=False)
        return jax.jit(lambda a: f(a).sum())

    a2a_fn = run(lambda b: jax.lax.all_to_all(b[0], "x", 0, 0,
                                              tiled=True)[None])
    ring_fn = run(lambda b: ring_exchange(b[0], "x", n)[None])
    want = np.asarray(x).reshape(n, n, C, width).transpose(1, 0, 2, 3)
    got = np.asarray(jax.shard_map(
        lambda b: ring_exchange(b[0], "x", n)[None], mesh=mesh,
        in_specs=P("x"), out_specs=P("x"), check_vma=False)(x))
    correct = bool(np.allclose(got, want, rtol=1e-6, atol=1e-6))
    if on_tpu:
        a2a_ms = timeit(a2a_fn, x)
        ring_ms = timeit(ring_fn, x)
        print(f"all_to_all bucket exchange : {a2a_ms:7.2f} ms", flush=True)
        print(f"pallas ring bucket exchange: {ring_ms:7.2f} ms  "
              f"correct={correct}", flush=True)
        MT.cell("ring/all_to_all", a2a_ms)
        MT.cell("ring/pallas", ring_ms, correct=float(correct))
        calibration.ab_verdict("ring_push", a2a_ms, ring_ms, correct,
                               shape=shape)
    else:
        print(f"pallas ring exchange (interpret): correct={correct}",
              flush=True)
        calibration.record_interpret("ring_push", correct, shape=shape)


if __name__ == "__main__":
    _init_telemetry(sys.argv)
    if "--ab-only" in sys.argv:
        pallas_ab()
        replica_ab()
        ring_ab()
    elif "--ring-ab" in sys.argv:
        ring_ab()
    else:
        exploratory_cells()
        if "--no-ab" not in sys.argv:
            pallas_ab()
            replica_ab()
            ring_ab()
    MT.close()
