#!/usr/bin/env python3
"""What a share's routing does under training, step by step: the
measurements behind ``configs/glm-4.7-flash-ep8.json``'s ``departures``
(as PR 31, PR 33 and PR 37 took them for their configurations), at the timed sizes:

    python3 benchmark/tools/mlalm_routing_drift.py [--steps N] [--rehearse-cpu]

Two variants of the cell's trainer, each ``--steps`` one-step calls on the
seed's own batches at a *constant* rate (the configuration's peak, after its
two warm-up steps), telemetry on, printing every step's
``held_pick_share`` (the stack's four expert layers and the module's
together), the module's own (``mtp_held_pick_share``) and (on the chip) its
wall time:

* ``constant_rate``: the cell as configured but for the schedule — the
  router's update withheld, the tokens' gradient through the routing weights
  cut — which shows what the zero rate of the timed window keeps still;
* ``router_trained``: the same with ``transformer.is_frozen`` letting the
  routers' updates through, the module's included (buffers stay fixed).

Prints one JSON line.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.families import mlalm as family     # noqa: E402
from benchmark.lib import spec                     # noqa: E402

CELL = "glm47f-ep8-8k-t8k"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=14)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.load_cell(CELL, rehearse=args.rehearse_cpu)
    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax

    from swiftmpi_tpu import obs
    from swiftmpi_tpu.models import trainer as trainer_mod
    from swiftmpi_tpu.models.transformer import is_buffer, is_frozen

    obs.set_enabled(True)             # the expert layers' counters
    constant = dict(cell.config, optimizer=dict(
        cell.config["optimizer"], decay_steps=10 ** 9))
    out = {"device": jax.devices()[0].device_kind, "steps": args.steps}
    for name, let_router in (("constant_rate", False),
                             ("router_trained", True)):
        # the name the trainer masks its updates by
        trainer_mod.is_frozen = (lambda path, cfg: is_buffer(path)) \
            if let_router else is_frozen
        with tempfile.TemporaryDirectory() as workdir:
            fam = family.Family(constant, cell.traffic, args.seed, workdir,
                                False, lambda n: contextlib.nullcontext())
            fam.make_inputs()
            fam.build_model()
            shares, module, ms, losses = [], [], [], []
            for _ in range(args.steps):
                t0 = time.perf_counter()
                _words, loss = fam.run_chunk(1)
                ms.append(round(1e3 * (time.perf_counter() - t0), 1))
                shares.append(round(fam.counters[-1]["held_pick_share"], 3))
                module.append(round(
                    fam.counters[-1]["mtp_held_pick_share"], 3))
                losses.append(round(loss, 4))
            out[name] = {"held_pick_share": shares,
                         "mtp_held_pick_share": module, "loss": losses,
                         "step_wall_ms": ms if jax.devices()[0].platform
                         == "tpu" else None}
            print(f"[drift] {name}: {json.dumps(out[name])}", flush=True)
            # 8.5 GB of parameters and moments: two variants' do not fit
            fam.state = fam.trainer = fam.fixed = None
            del fam
            jax.clear_caches()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
