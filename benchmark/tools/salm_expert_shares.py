#!/usr/bin/env python3
"""Which 16 of ``keye-vl-2.0-30b-a3b-ep8``'s 128 experts this chip holds:
the share of the routers' picks each of the eight ranges ``[16 j, 16 j + 16)``
— the eight chips of the deployment — gets on the fixed weights, at the real
size (counts, no time; on the chip, where a forward pass of 16,384 positions
through the sparse layers takes seconds, with the configuration's operands):

    python3 benchmark/tools/salm_expert_shares.py [--ties] [--rehearse-cpu]

For each range the stack runs forward on the first batch of the fixed stream
with that range held (what the absent experts would add is left out, so the
later layers' inputs depend on the range), and the picks of the four expert
layers that land in the range are counted.  PR 33's rule holds the range
whose share is nearest the even one (12.5 %).  ``--ties`` runs the
configuration's own range alone and counts, per gap, the expert-layer tokens
whose 8th and 9th router scores lie closer than it (what ``check.tie_gap``
leaves out of the per-token comparison).  Prints one JSON line.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.families import salm as family      # noqa: E402
from benchmark.lib import spec                     # noqa: E402

CELL = "keye2-ep8-16k-t16k"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ties", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.load_cell(CELL, rehearse=args.rehearse_cpu)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from swiftmpi_tpu.models import transformer as tfm
    from swiftmpi_tpu.parallel import moe

    with tempfile.TemporaryDirectory() as workdir:
        # the seed makes the held-out sequence alone: the stream is fixed
        fam = family.Family(cell.config, cell.traffic, 0, workdir, False,
                            lambda name: contextlib.nullcontext())
        fam.make_inputs()
    batch = fam.sequences[:fam.seqs]
    base = dataclasses.replace(
        family.transformer_config(cell.config, cell.traffic), remat=False)
    params = tfm.init_params(jax.random.key(family.WEIGHTS_KEY), base)
    E, k = base.n_experts, base.moe_top_k
    width = base.held[1] - base.held[0]
    gaps = (1e-4, 2e-5, 1e-5, 1e-6)

    def picks(cfg, params, batch):
        """((expert layers, E) picks by expert; (expert layers, len(gaps))
        tokens nearer a tie than each gap)."""
        hs = tfm.hidden_states(params, batch, cfg)
        out, ties = [], []
        layers = [jax.tree.map(lambda a, i=i: a[i], stacked)
                  for stacked in params["blocks"]
                  for i in range(stacked["ln1"].shape[0])]
        for i, blk in enumerate(layers):
            u = tfm._rms_norm(hs[2 * i + 1], blk["ln2"], cfg.norm_eps
                              ).reshape(-1, cfg.d_model)
            sel, *_ = moe.route(u, blk["moe"].router, blk["moe"].bias, k,
                                cfg.router, cfg.route_scale)
            out.append(jnp.bincount(sel.reshape(-1), length=E))
            scores = jax.nn.softmax(jnp.dot(
                u, blk["moe"].router, precision=jax.lax.Precision.HIGHEST))
            top, _ = jax.lax.top_k(scores, k + 1)
            gap = top[:, -2] - top[:, -1]
            ties.append(jnp.stack([(gap < g).sum() for g in gaps]))
        return jnp.stack(out), jnp.stack(ties)

    out = {"stream_seed": family.STREAM_SEED, "tokens": int(batch.size),
           "ranges": []}
    ranges = [base.held[0] // width] if args.ties else range(E // width)
    for j in ranges:
        cfg = dataclasses.replace(base, experts_held=(j * width,
                                                      (j + 1) * width))
        counts, ties = (np.asarray(a) for a in jax.jit(
            lambda p, b, cfg=cfg: picks(cfg, p, b))(params, batch))
        mine = counts[:, j * width:(j + 1) * width].sum(1)
        out["ranges"].append({
            "held": [j * width, (j + 1) * width],
            "share": round(100.0 * float(mine.sum() / counts.sum()), 3),
            "by_layer": [round(100.0 * float(a / b), 2)
                         for a, b in zip(mine, counts.sum(1))],
            "near_tie_share": {
                str(g): round(100.0 * float(t) / (len(ties) * batch.size), 4)
                for g, t in zip(gaps, ties.sum(0))}})
        jax.clear_caches()
    out["nearest_even"] = min(
        out["ranges"], key=lambda r: abs(r["share"] - 100.0 * width / E)
    )["held"]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
