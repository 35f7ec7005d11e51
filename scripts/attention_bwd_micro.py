#!/usr/bin/env python3
"""Blockwise attention's backward alone, and its two forward walks side by
side, at the LM cells' shapes and masks.

    python scripts/attention_bwd_micro.py [--tree DIR] [--cases a,b] [--calls 10]
    JAX_PLATFORMS=cpu python scripts/attention_bwd_micro.py --compile-only

On the chip: for each case the hand-written backward
(``parallel/ring_attention.py::_blockwise_vjp_bwd`` on the forward's own
residuals and a random bf16 ``do``) is timed over ``--calls`` fenced calls
after a warm-up, then two more calls are traced and reduced to self time by
device op (``benchmark/lib/xplane.py``).  The forward is timed twice where
the tree has the kernel: the XLA walk (``_blockwise_fwd``, ``fwd_xla_ms``)
and the Pallas kernel (``parallel/attention_kernel.py::attn_fwd_tiles``,
``fwd_tiles_ms`` the call, ``fwd_kernel_ms`` the kernel's own device time
from a trace), with the largest difference of their ``o`` and ``lse``, the
VMEM the kernel asks for and how it cuts a query tile's ``size x G`` rows
(``row_cut``: the queries a product takes of one head, and the query heads
a grid step holds).
``--tree DIR`` imports
``swiftmpi_tpu`` from another checkout (the parent's, unpacked under a
directory ``.gitignore`` lists), so two commits are compared by two
processes of one chip call.  One JSON line a case; all of them in
``chiprun_out/attention_bwd_micro[.<tag>].json``.

``--rehearse-cpu`` walks the same path at a 16th of the sequence and
prints no time.  ``--compile-only`` lowers the same backward for a
described v5e instead (no chip, no time): temporaries, peak, and every
``dynamic-update-slice`` the compiled text holds with its operand's shape
and layout.

Cases (B, S, H / Hkv heads of D, tile 512): ``sdar`` 2, 16,384, 32 / 4 of
128, ``BlockDiffusionMask(8192, 4)``; ``window`` and ``full`` 1, 16,384,
32 / 4 of 128 under ``WindowMask(2048)`` and causal (Trinity's two kinds);
``lfm2`` 4, 8,192, 32 / 8 of 64, causal; ``glm47f`` 1, 8,192, 20 / 20 of
256, causal (``G`` = 1); ``nemotron3n`` 1, 8,192, 32 / 2 of 128, causal
(``G`` = 16); ``g1`` = ``sdar`` with 32 KV heads (plain multi-head: the case
no benchmark cell holds).
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import re
import statistics
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CASES = {   # name: (B, S, H, Hkv, D, mask)
    "sdar": (2, 16384, 32, 4, 128, "bd"),
    "window": (1, 16384, 32, 4, 128, "window"),
    "full": (1, 16384, 32, 4, 128, "causal"),
    "lfm2": (4, 8192, 32, 8, 64, "causal"),
    "glm47f": (1, 8192, 20, 20, 256, "causal"),
    "nemotron3n": (1, 8192, 32, 2, 128, "causal"),
    "g1": (2, 16384, 32, 32, 128, "bd"),
}
TILE = 512


def _mask(kind, S):
    from swiftmpi_tpu.models.diffusion import BlockDiffusionMask
    ra = importlib.import_module("swiftmpi_tpu.parallel.ring_attention")
    return {"bd": BlockDiffusionMask(S // 2, 4),
            "window": ra.WindowMask(2048), "causal": ra.CAUSAL}[kind]


def _shapes(B, S, H, Hkv, D):
    q = (B, S, Hkv, H // Hkv, D)
    return q, (B, S, Hkv, D), (B, S, Hkv, D), q        # q, k, v, do


def compile_only(names):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    ra = importlib.import_module("swiftmpi_tpu.parallel.ring_attention")

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    for name in names:
        B, S, H, Hkv, D, kind = CASES[name]
        mask = _mask(kind, S)
        sq, sk, sv, sdo = (jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one)
                           for s in _shapes(B, S, H, Hkv, D))
        lse = jax.ShapeDtypeStruct(sq.shape[:-1], jnp.float32, sharding=one)
        compiled = jax.jit(
            lambda q, k, v, o, lse, do: ra._blockwise_vjp_bwd(
                TILE, mask, (q, k, v, None, o, lse), (do, None))).lower(
                    sq, sk, sv, sq, lse, sdo).compile()
        text = compiled.as_text()
        # bare, or the root of a fusion's computation: both read this way
        dus = sorted(set(re.findall(
            r"= (\w+\[[\d,]*\](?:\{[^}]*\})?) dynamic-update-slice\(", text)))
        mem = compiled.memory_analysis()
        print(json.dumps({
            "case": name, "compile_only": True,
            "temp_gib": mem.temp_size_in_bytes / 2 ** 30,
            "peak_gib": mem.peak_memory_in_bytes / 2 ** 30,
            "dynamic_update_slices": dus}), flush=True)


def _kernel_ms(fn, *args, calls=3) -> float:
    """Device ms a call of the ops named ``attn_fwd_tiles`` in a trace of
    ``calls`` calls: the kernel alone.  (Called alone, the kernel's
    sequence-minor operands are relayout copies around it, which
    ``fwd_tiles_ms`` includes; in a step the compiler keeps ``q``, ``v``
    and ``o`` that way and they are bitcasts.)"""
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
    return xplane.matching_seconds(d, window, "attn_fwd_tiles")[0] \
        * 1e3 / calls


def _forward_walks(ra, mask, q, k, v, timed, rehearse) -> dict:
    """The two forward walks at one case, where the tree has the kernel:
    their times (none on a CPU rehearsal, where the kernel is interpreted),
    how far apart their results lie, and what the kernel asked for."""
    import jax
    import jax.numpy as jnp
    try:
        ak = importlib.import_module("swiftmpi_tpu.parallel.attention_kernel")
    except ImportError:
        return {}
    G, D = q.shape[3:]
    if not ak.takes(q.dtype, TILE, q.shape[2], G, D):
        return {"forward_walk": "xla"}
    xla = jax.jit(lambda q, k, v: ra._blockwise_fwd(q, k, v, None, TILE,
                                                    mask))
    tiles = jax.jit(lambda q, k, v: ak.attn_fwd_tiles(q, k, v, None, TILE,
                                                      mask))
    if rehearse:
        from jax.experimental.pallas import tpu as pltpu
        with pltpu.force_tpu_interpret_mode():
            got = jax.block_until_ready(tiles(q, k, v))
        out = {}
    else:
        out = {"fwd_xla_ms": statistics.median(timed(xla, q, k, v)),
               "fwd_tiles_ms": statistics.median(timed(tiles, q, k, v)),
               "fwd_kernel_ms": _kernel_ms(tiles, q, k, v)}
        got = tiles(q, k, v)
    want = xla(q, k, v)
    out.update(
        forward_walk="tiles",
        fwd_o_max_abs_diff=float(jnp.abs(
            got[0].astype(jnp.float32) - want[0].astype(jnp.float32)).max()),
        fwd_lse_max_abs_diff=float(jnp.abs(got[1] - want[1]).max()),
        kernel_vmem_mib=ak.vmem_bytes(
            q.dtype, TILE, ak.heads_per_step(q.shape[2], G, D) * G, D)
        / 2 ** 20,
        row_cut=[TILE, ak.heads_per_step(q.shape[2], G, D) * G],
        pairs=len(ak.tile_pairs(mask, q.shape[1] // TILE, TILE)[0]))
    return out


def measure(names, calls, out, rehearse):
    import jax
    import jax.numpy as jnp

    from benchmark.lib import xplane
    ra = importlib.import_module("swiftmpi_tpu.parallel.ring_attention")

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not rehearse:
        raise SystemExit(f"no TPU here ({dev.platform}): a time comes only "
                         "from the chip; --compile-only needs none")
    rows = []
    for name in names:
        B, S, H, Hkv, D, kind = CASES[name]
        if rehearse:                  # the control flow, at a 16th of S
            S //= 16
        mask = _mask(kind, S)
        keys = jax.random.split(jax.random.key(39), 4)
        q, k, v, do = (jax.random.normal(kk, s, jnp.bfloat16)
                       for kk, s in zip(keys, _shapes(B, S, H, Hkv, D)))
        fwd = jax.jit(lambda q, k, v: ra._blockwise_vjp_fwd(q, k, v, None,
                                                            TILE, mask))
        bwd = jax.jit(lambda res, do: ra._blockwise_vjp_bwd(TILE, mask, res,
                                                            (do, None)))

        def timed(fn, *a):
            jax.block_until_ready(fn(*a))
            ms = []
            for _ in range(calls):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*a))
                ms.append((time.perf_counter() - t0) * 1e3)
            return ms

        fwd_ms = timed(fwd, q, k, v)
        _o, res = fwd(q, k, v)
        walks = _forward_walks(ra, mask, q, k, v, timed, rehearse)
        bwd_ms = timed(bwd, res, do)
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            for _ in range(2):
                jax.block_until_ready(bwd(res, do))
            jax.profiler.stop_trace()
            trace = xplane.load(glob.glob(
                os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0])
        row = {"case": name, "shape": [B, S, H, Hkv, D], "mask": kind,
               "tree": os.path.dirname(os.path.dirname(os.path.dirname(
                   os.path.abspath(ra.__file__))))}
        row.update(walks)
        if rehearse:
            row["rehearse_cpu"] = True
        else:
            d = trace.devices[0]
            window = (min(e[0] for e in d.ops), max(e[1] for e in d.ops))
            ops = xplane.op_seconds(d, window, xplane.op_group)
            row.update(
                device=dev.device_kind, calls=calls,
                bwd_ms_median=statistics.median(bwd_ms),
                bwd_ms_min=min(bwd_ms), bwd_ms_max=max(bwd_ms),
                fwd_ms_median=statistics.median(fwd_ms),
                traced_busy_ms_per_call=xplane.busy_seconds(d, window)
                * 1e3 / 2,
                traced_ops_ms_per_call=[[n, s * 1e3 / 2]
                                        for n, s in xplane.top(ops, 12)])
        print(json.dumps(row), flush=True)
        rows.append(row)
        del q, k, v, do, res, _o
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=REPO)
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--tag", default="")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, REPO)                      # benchmark.lib.xplane
    sys.path.insert(0, os.path.abspath(args.tree))
    names = args.cases.split(",")
    if args.compile_only:
        compile_only(names)
    else:
        measure(names, args.calls, os.path.join(
            REPO, "chiprun_out", "attention_bwd_micro"
            + (f".{args.tag}" if args.tag else "") + ".json"),
            args.rehearse_cpu)
    return 0


if __name__ == "__main__":
    sys.exit(main())
