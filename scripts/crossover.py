#!/usr/bin/env python
"""Backend crossover study: xla-sparse vs xla-dense vs tpu(all_to_all)
push/pull cost across table capacity x push-batch size (SURVEY §7 hard
part (a); VERDICT round-1 'next' #7).

Times one pull + one push (w2v access, d=100) per (backend, capacity, B)
cell on the current default platform, fenced by a D2H read.  Emits
one JSON line per cell plus a summary table and the measured
sparse->dense crossover ratio per capacity; the numbers behind
docs/ARCHITECTURE.md's "push backend selection" section and
XlaTransfer's auto heuristic.

Run CPU: JAX_PLATFORMS=cpu \
           XLA_FLAGS=--xla_force_host_platform_device_count=8 \
           python scripts/crossover.py
Run on the chip: python scripts/crossover.py --single-device
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# must precede jax import (see swiftmpi_tpu/utils/xla_env.py)
from swiftmpi_tpu.utils.xla_env import ensure_cpu_mesh_flags  # noqa: E402

ensure_cpu_mesh_flags()

import numpy as np  # noqa: E402


CAPS = (32_768, 262_144, 1_048_576)
BATCHES = (4096, 65_536, 524_288)
BACKEND_NAMES = ("xla_sparse", "xla_dense", "tpu_a2a")
CELL_TIMEOUT_S = 300


def run_cell(name, cap_total, B, d, reps, single_device):
    """One (backend, capacity, batch) measurement; returns the cell dict.
    Runs inside its own subprocess (--cell): an XLA:CPU collective
    deadlock (observed: 5/8 rendezvous threads arriving, forever, at
    tpu_a2a B>=64K on the virtual mesh) then costs one cell and a
    timeout, not the whole study."""
    import jax
    import jax.numpy as jnp
    from swiftmpi_tpu.cluster import ps_mesh
    from swiftmpi_tpu.parameter import KeyIndex, SparseTable, w2v_access
    from swiftmpi_tpu.transfer.tpu import TpuTransfer
    from swiftmpi_tpu.transfer.xla import XlaTransfer

    access = w2v_access(0.7, d)
    n_dev = len(jax.devices())
    if name == "xla_sparse":
        backend = XlaTransfer(dense_apply=False)
    elif name == "xla_dense":
        backend = XlaTransfer(dense_apply=True)
    elif name == "tpu_a2a":
        if single_device or n_dev < 2:
            return {"backend": name, "capacity": cap_total, "batch": B,
                    "error": "skipped: needs a multi-device mesh"}
        backend = TpuTransfer(ps_mesh())
    else:
        raise ValueError(name)

    def fence(x):
        return float(jax.tree_util.tree_leaves(x)[0].reshape(-1)[0])

    shards = n_dev if name == "tpu_a2a" else 1
    ki = KeyIndex(num_shards=shards, capacity_per_shard=cap_total // shards)
    mesh = ps_mesh() if shards > 1 else None
    table = SparseTable(access, ki, mesh=mesh,
                        axis="shard" if mesh else "model")
    rng = np.random.default_rng(0)
    slots = (rng.integers(0, cap_total, size=B)).astype(np.int32)
    grads = {f: jnp.asarray(rng.normal(size=(B, d)).astype(np.float32))
             for f in access.grad_fields}
    sj = jnp.asarray(slots)
    state = {f: jnp.array(v) for f, v in table.state.items()}
    try:
        out = backend.push(state, sj, grads, access)
        fence(out)                       # compile + settle
        t0 = time.perf_counter()
        for _ in range(reps):
            out = backend.push(state, sj, grads, access)
        fence(out)
        push_ms = (time.perf_counter() - t0) / reps * 1e3
        rows = backend.pull(state, sj, access)
        fence(rows)
        t0 = time.perf_counter()
        for _ in range(reps):
            rows = backend.pull(state, sj, access)
        fence(rows)
        pull_ms = (time.perf_counter() - t0) / reps * 1e3
        return {"backend": name, "capacity": cap_total, "batch": B,
                "push_ms": round(push_ms, 3), "pull_ms": round(pull_ms, 3)}
    except Exception as e:
        return {"backend": name, "capacity": cap_total, "batch": B,
                "error": f"{type(e).__name__}: {e}"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--single-device", action="store_true",
                    help="skip the 8-device tpu backend (1 real chip)")
    ap.add_argument("--d", type=int, default=100)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--cell", default=None,
                    help="internal: run one backend:cap:B cell inline")
    args = ap.parse_args()

    if args.cell:
        name, cap, B = args.cell.split(":")
        cell = run_cell(name, int(cap), int(B), args.d, args.reps,
                        args.single_device)
        print("CELL " + json.dumps(cell), flush=True)
        return

    import subprocess
    results = []
    a2a_unavailable = False
    for cap_total in CAPS:
        for B in BATCHES:
            for name in BACKEND_NAMES:
                if name == "tpu_a2a" and (args.single_device
                                          or a2a_unavailable):
                    continue
                cmd = [sys.executable, os.path.abspath(__file__),
                       "--cell", f"{name}:{cap_total}:{B}",
                       "--d", str(args.d), "--reps", str(args.reps)]
                if args.single_device:
                    cmd.append("--single-device")
                try:
                    p = subprocess.run(cmd, capture_output=True, text=True,
                                       timeout=CELL_TIMEOUT_S)
                    cell = None
                    for ln in reversed(p.stdout.splitlines()):
                        if ln.startswith("CELL "):
                            cell = json.loads(ln[5:])
                            break
                    if cell is None:
                        tail = (p.stderr or "").strip().splitlines()[-2:]
                        cell = {"backend": name, "capacity": cap_total,
                                "batch": B,
                                "error": f"rc={p.returncode}: "
                                         f"{' | '.join(tail)}"}
                except subprocess.TimeoutExpired:
                    cell = {"backend": name, "capacity": cap_total,
                            "batch": B,
                            "error": f"timeout {CELL_TIMEOUT_S}s "
                                     "(XLA:CPU collective deadlock?)"}
                if name == "tpu_a2a" and "skipped" in str(
                        cell.get("error", "")):
                    # single-device child: don't pay 8 more JAX cold
                    # starts for identical skip records
                    a2a_unavailable = True
                results.append(cell)
                print(json.dumps(cell), flush=True)

    # crossover summary: smallest B/capacity where dense beats sparse
    print("\n== sparse vs dense push crossover ==")
    for cap in sorted({r["capacity"] for r in results}):
        line = [f"cap={cap:>9}"]
        for B in sorted({r["batch"] for r in results}):
            sp = next((r for r in results
                       if r["backend"] == "xla_sparse"
                       and r["capacity"] == cap and r["batch"] == B), {})
            de = next((r for r in results
                       if r["backend"] == "xla_dense"
                       and r["capacity"] == cap and r["batch"] == B), {})
            if "push_ms" in sp and "push_ms" in de:
                win = "dense" if de["push_ms"] < sp["push_ms"] else "sparse"
                line.append(f"B={B}: {win} "
                            f"({de['push_ms']:.1f} vs {sp['push_ms']:.1f})")
        print("  ".join(line))


if __name__ == "__main__":
    main()
