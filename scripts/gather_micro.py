#!/usr/bin/env python
"""Microbench: XLA row-gather / scatter-add throughput on the live chip.

The w2v step's batch work is gathers and scatters (PERF.md section
5).  This asks what the hardware path can actually sustain under
layouts we control:

  * row width 100 (demo.conf len_vec) vs 128 (lane-aligned)
  * fp32 vs bf16 rows
  * table capacity 17K vs 256K (cache/locality effect)
  * gather vs scatter-add vs sort+segment-sum

Run (on the chip): python scripts/gather_micro.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402


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


def _init_telemetry(argv, run="gather_micro"):
    global MT
    if "--telemetry" in argv:
        path = argv[argv.index("--telemetry") + 1]
        from swiftmpi_tpu.obs.micro import MicroTelemetry
        import jax
        MT = MicroTelemetry(path, run=run,
                            meta={"device": str(jax.devices()[0])})
        print(f"telemetry -> {path}", flush=True)


def timeit(fn, *args, reps=16):
    import jax
    out = fn(*args)
    jax.tree_util.tree_leaves(out)[0].block_until_ready()
    np.asarray(jax.tree_util.tree_leaves(out)[0]).reshape(-1)[:1]  # D2H fence
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    float(np.asarray(jax.tree_util.tree_leaves(out)[0]).reshape(-1)[0])
    return (time.perf_counter() - t0) / reps


def locality_cells():
    """Decision diagnostics (also folded into the full grid).

    H2D: does the per-batch H2D stream (~140MB/epoch of stacked
    centers/contexts/masks at text8 scale) explain an epoch wall above
    the sum of its steady-state steps?  If measured GB/s puts 140MB
    near the gap, a ship-tokens-once device-side batcher is the next
    text8 attack; if H2D is fast, the gap is dispatch/queue latency
    and fatter scan groups are.

    gather1m (VERDICT #4 decision data): at cap=1.3M the table is
    ~520MB and random rows may thrash DRAM pages where the demo-scale
    table did not.  Random vs sorted vs contiguous bounds the locality
    headroom: if sorted ≈ contiguous ≪ random, an in-step
    argsort(+unpermute, itself a row-local gather) could pay; if
    random ≈ sorted, the 1M step's gap vs its transaction floor lives
    elsewhere (see profile_1m)."""
    import jax
    import jax.numpy as jnp

    N = 344_064          # bench gather count: B*(K+1) at B=16384, K=20
    rng = np.random.default_rng(0)
    print(f"device: {jax.devices()[0]}", flush=True)

    def _bracket(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    for mb in (8, 64):
        nbytes = mb * 1024 * 1024
        host = np.random.default_rng(1).integers(
            0, 1 << 30, size=(nbytes // 4,)).astype(np.int32)
        put = lambda a: jax.device_put(a).block_until_ready()
        put(host)                 # warm the large-transfer path too
        # min of several reps, like the gather cells — one transfer
        # is a noisy sample and this number decides between two
        # different text8 attacks
        dt = min(_bracket(lambda: put(host)) for _ in range(4))
        print(f"h2d     {mb:3d} MB  {dt * 1e3:7.2f} ms  "
              f"{nbytes / 1e9 / dt:6.2f} GB/s", flush=True)

    cap1m, d = 1_300_001, 100
    table = jnp.asarray(rng.standard_normal((cap1m, d)), jnp.float32)
    take = jax.jit(lambda t, i: jnp.take(t, i, axis=0).sum())
    for label, arr in (
            ("random", rng.integers(0, cap1m, N)),
            ("sorted", np.sort(rng.integers(0, cap1m, N))),
            # truly contiguous (rows 0..N-1): a strided or sorted-draw
            # pattern has nearly the same inter-row gap distribution as
            # "sorted" and would make the comparison vacuous
            ("sequential", np.arange(N))):
        idx = jnp.asarray(arr, jnp.int32)
        ms = timeit(take, table, idx) * 1e3
        print(f"gather1m cap={cap1m} d={d} {label:10s} {ms:7.2f} ms  "
              f"{N * d * 4 / 1e9 / ms * 1e3:6.1f} GB/s", flush=True)


def main(ab=True):
    import jax
    import jax.numpy as jnp

    N = 344_064          # bench gather count: B*(K+1) at B=16384, K=20
    rng = np.random.default_rng(0)

    locality_cells()              # prints the device line

    for cap in (17_314, 262_144):
        idx = jnp.asarray(rng.integers(0, cap, N), jnp.int32)
        for d in (100, 128):
            for dt in (jnp.float32, jnp.bfloat16):
                table = jnp.asarray(
                    rng.standard_normal((cap, d)), dt)
                take = jax.jit(lambda t, i: jnp.take(t, i, axis=0).sum())
                ms = timeit(take, table, idx) * 1e3
                gb = N * d * table.dtype.itemsize / 1e9
                print(f"gather  cap={cap:7d} d={d} {table.dtype.name:9s}"
                      f" {ms:7.2f} ms  {gb / ms * 1e3:6.1f} GB/s", flush=True)
                MT.cell(f"gather/cap{cap}_d{d}_{table.dtype.name}", ms,
                        gbps=gb / ms * 1e3)

        # scatter-add and sort+segment paths at d=100 fp32
        d = 100
        table = jnp.asarray(rng.standard_normal((cap, d)), jnp.float32)
        g = jnp.asarray(rng.standard_normal((N, d)), jnp.float32)

        scat = jax.jit(lambda t, i, g: t.at[i].add(g))
        ms = timeit(scat, table, idx, g) * 1e3
        print(f"scatter+ cap={cap:7d} d={d} float32   {ms:7.2f} ms",
              flush=True)
        MT.cell(f"scatter/cap{cap}_d{d}_float32", ms)

        def sort_seg(i, g):
            order = jnp.argsort(i)
            si = i[order]
            sg = g[order]
            new = jnp.concatenate([jnp.ones((1,), jnp.int32),
                                   (si[1:] != si[:-1]).astype(jnp.int32)])
            seg = jnp.cumsum(new) - 1
            acc = jnp.zeros((N, d), jnp.float32).at[seg].add(sg)
            return acc.sum()
        ms = timeit(jax.jit(sort_seg), idx, g) * 1e3
        print(f"sort+seg cap={cap:7d} d={d} float32   {ms:7.2f} ms",
              flush=True)

    # one-hot matmul gather-equivalent at bench shape (MXU alternative)
    cap = 17_314
    B, K1 = 16_384, 21
    table = jnp.asarray(rng.standard_normal((cap, 100)), jnp.bfloat16)
    idx2 = jnp.asarray(rng.integers(0, cap, (B, K1)), jnp.int32)

    def onehot_mm(t, i):
        oh = jax.nn.one_hot(i.reshape(-1), cap, dtype=jnp.bfloat16)
        return (oh @ t).sum()
    ms = timeit(jax.jit(onehot_mm), table, idx2) * 1e3
    print(f"onehot-matmul gather (bf16, cap=17314): {ms:7.2f} ms", flush=True)

    # bf16 VMEM gather: with the kernel byte-bound (unlike XLA's
    # transaction-bound HBM gather), half-width rows may halve the time
    from swiftmpi_tpu.ops.pallas_gather import fits_vmem, vmem_gather
    tb16 = jnp.asarray(rng.standard_normal((cap, 100)), jnp.bfloat16)
    idxg = jnp.asarray(rng.integers(0, cap, N), jnp.int32)
    if fits_vmem(tb16):
        try:
            pg16 = jax.jit(lambda t, i: vmem_gather(t, i).sum())
            ms = timeit(pg16, tb16, idxg) * 1e3
            print(f"pallas vmem gather (bf16, cap=17314): {ms:7.2f} ms",
                  flush=True)
        except Exception as e:
            print(f"pallas vmem gather bf16: UNSUPPORTED "
                  f"({type(e).__name__}: {str(e)[:160]})", flush=True)

    if ab:
        pallas_ab()


def dense_cells():
    """Dense vocab-matmul rendering of the parity step — measured piece
    by piece.  Idea: with capacity ~17K, compute FULL logits
    F = neu1 @ h.T on the MXU, then f[b,k] = F[b, t[b,k]] is a
    ROW-LOCAL scalar gather (21 elements within one contiguous 69KB
    row) instead of 344K random 400B row fetches; likewise the h-grad
    becomes G.T @ neu1 (MXU) after a row-local scalar scatter.  Same
    math, same sampling stream, different memory shape.  If these cells
    beat gather+scatter (~7ms at bench shape), a `dense_logits` parity
    mode is worth wiring."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    cap, B, K1, d = 17_314, 16_384, 21, 100
    h = jnp.asarray(rng.standard_normal((cap, d)), jnp.float32)
    neu1 = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    tidx = jnp.asarray(rng.integers(0, cap, (B, K1)), jnp.int32)
    gvals = jnp.asarray(rng.standard_normal((B, K1)), jnp.float32)
    print(f"dense cells device: {jax.devices()[0]}", flush=True)
    for dt in (jnp.float32, jnp.bfloat16):
        hh, nn = h.astype(dt), neu1.astype(dt)
        ms = timeit(jax.jit(lambda a, b: (a @ b.T).sum()), nn, hh) * 1e3
        print(f"F = neu1 @ h.T   ({jnp.dtype(dt).name:8s}): {ms:7.2f} ms",
              flush=True)
    fpair = jax.jit(lambda a, b, i:
                    jnp.take_along_axis(a @ b.T, i, axis=1).sum())
    ms = timeit(fpair, neu1, h, tidx) * 1e3
    print(f"F + row-local pair gather (fp32):  {ms:7.2f} ms", flush=True)
    rows = jnp.arange(B)[:, None]
    gscat = jax.jit(lambda g, i: jnp.zeros((B, cap), jnp.float32)
                    .at[rows, i].add(g).sum())
    ms = timeit(gscat, gvals, tidx) * 1e3
    print(f"row-local scalar scatter (B,cap):  {ms:7.2f} ms", flush=True)
    G = jnp.asarray(rng.standard_normal((B, cap)), jnp.bfloat16)
    nb = neu1.astype(jnp.bfloat16)
    ms = timeit(jax.jit(lambda G, n: (G.T @ n).sum()), G, nb) * 1e3
    print(f"G.T @ neu1 grad matmul (bf16):     {ms:7.2f} ms", flush=True)
    # end-to-end fused candidate: logits -> pair gather -> scalar
    # scatter -> grad matmul, one jit (lets XLA fuse what it can)
    alpha = 0.05

    def fused(nn, hh, i):
        F = nn @ hh.T                                    # (B, cap)
        f = jnp.take_along_axis(F, i, axis=1)            # (B, K1)
        g = (1.0 - jax.nn.sigmoid(f)) * alpha
        G = jnp.zeros((B, cap), jnp.float32).at[rows, i].add(g)
        hgrad = G.T @ nn                                 # (cap, d) MXU
        neu1e = G @ hh                                   # (B, d)  MXU
        return hgrad.sum() + neu1e.sum()

    ms = timeit(jax.jit(fused), neu1, h, tidx) * 1e3
    print(f"fused dense-logits NS phase (fp32):{ms:7.2f} ms", flush=True)


def pallas_ab():
    """Pallas VMEM-resident gather (ops/pallas_gather.py) vs XLA's HBM
    gather at the bench shape — the "does XLA fall short?" experiment.
    Records the verdict via ops/calibration so the pull path's
    measurement-driven gate (transfer/xla.py) flips on a real win."""
    import jax
    import jax.numpy as jnp

    from swiftmpi_tpu.ops import calibration
    from swiftmpi_tpu.ops.pallas_gather import fits_vmem, vmem_gather

    rng = np.random.default_rng(0)
    cap = 17_314
    tf32 = jnp.asarray(rng.standard_normal((cap, 100)), jnp.float32)
    N = 344_064
    idx3 = jnp.asarray(rng.integers(0, cap, N), jnp.int32)
    print(f"A/B device: {jax.devices()[0]}", flush=True)

    xla_take = jax.jit(lambda t, i: jnp.take(t, i, axis=0).sum())
    xla_ms = timeit(xla_take, tf32, idx3) * 1e3
    gb = N * 100 * 4 / 1e9
    print(f"xla gather    (fp32, cap={cap}): {xla_ms:7.2f} ms  "
          f"{gb / xla_ms * 1e3:6.1f} GB/s", flush=True)
    MT.cell("xla_gather/cap17314_d100_fp32", xla_ms)
    if not fits_vmem(tf32):
        return
    small_idx = idx3[:8192]
    want = np.asarray(jnp.take(tf32, small_idx, axis=0))
    variants = {}      # full per-variant record, kept in the verdict
    # the kernel's grid-step size is a tuning knob worth two cells
    for blk in (4096, 16384):
        tag = f"loop{blk}"
        try:
            # correctness first: a Mosaic-lowering divergence must
            # never flip the gate onto wrong numerics (slice must be a
            # block multiple: one block for big-block variants)
            chk = idx3[:max(8192, blk)]
            want_chk = want if chk.shape[0] == small_idx.shape[0] \
                else np.asarray(jnp.take(tf32, chk, axis=0))
            got = np.asarray(vmem_gather(tf32, chk, idx_block=blk))
            correct = bool(np.allclose(got, want_chk))
            pg = jax.jit(lambda t, i, b=blk:
                         vmem_gather(t, i, idx_block=b).sum())
            ms = timeit(pg, tf32, idx3) * 1e3
            print(f"pallas vmem gather[{tag}] (fp32, cap={cap}): "
                  f"{ms:7.2f} ms  {gb / ms * 1e3:6.1f} GB/s  "
                  f"correct={correct}", flush=True)
            MT.cell(f"pallas_gather/{tag}", ms, correct=float(correct))
            variants[tag] = {"correct": correct, "ms": round(ms, 3),
                             "idx_block": blk}
        except Exception as e:
            msg = f"{type(e).__name__}: {str(e)[:160]}"
            variants[tag] = {"error": msg}
            print(f"pallas vmem gather[{tag}]: UNSUPPORTED ({msg})",
                  flush=True)
    usable = {t: v["ms"] for t, v in variants.items()
              if v.get("correct")}
    if usable:
        best = min(usable, key=usable.get)
        calibration.ab_verdict("vmem_gather", xla_ms, usable[best],
                               correct=True,
                               shape=f"cap={cap} d=100 fp32 N={N}",
                               extra={"idx_block": variants[best]["idx_block"],
                                      "variants": variants})
    else:
        # keep the per-variant record: an operator must be able to tell
        # a lowering failure from a numerics divergence
        calibration.ab_verdict("vmem_gather", xla_ms,
                               error="no correct variant",
                               extra={"variants": variants})


if __name__ == "__main__":
    _init_telemetry(sys.argv)
    if "--ab-only" in sys.argv:
        pallas_ab()
    elif "--dense-only" in sys.argv:
        dense_cells()
    elif "--locality-only" in sys.argv:
        locality_cells()
    else:
        main(ab="--no-ab" not in sys.argv)
    MT.close()
