#!/usr/bin/env python3
"""The second of the two readings a limit of ``nemotron3n-ep16-8k-t8k``'s
first-step comparison lies between, on the chip at the timed sizes
(``tools/lm_lower_precision.py``'s method):

    python3 benchmark/tools/sslm_lower_precision.py [--seed N] [--rehearse-cpu]

The first reading is what the program (bf16 operands; float32 decays, running
sums and chunk states, as the configuration states) gives against the plain
reference: ``Family.first_step_check``'s own numbers, printed by every run of
the cell.  This tool gives the second: what the *reference* gives in the
nearest precision below the stated one, against the true reference, on the
same inputs, in three readings: the recurrence's decays and state held in
bfloat16 (``ssm_decay_state_and_sums`` states float32), every large
product's operands rounded to float8 e4m3 (``matmul_operands`` states
bfloat16), and — which should land near the first reading — operands rounded
to bfloat16.  The first two must come out as not correct by
``families/sslm.py::LIMITS``.  Prints one JSON line; a chip run of its own.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.families import sslm as family      # noqa: E402
from benchmark.lib import spec                     # noqa: E402
from benchmark.reference import sslm as reference  # noqa: E402

CELL = "nemotron3n-ep16-8k-t8k"
READINGS = {"bfloat16_ssm_decays_and_state": {"ssm_dtype": "bfloat16"},
            "float8_e4m3fn_operands": {"operands": "float8_e4m3fn"},
            "bfloat16_operands": {"operands": "bfloat16"}}


def lowered_reading(fam, batch, hs, want, loss, lower: dict) -> dict:
    """Errors of the reference with ``lower`` laid over its dims against the
    true reference: the loss, per kind of half layer the largest per-token
    update error on the program's inputs, and per sampled tensor the
    gradient's (``want``: the true reference's sampled gradients)."""
    import jax.numpy as jnp

    params = fam.state.params
    low = reference.Reference({**fam.dims, **lower})
    worst = {"loss": abs(low.loss(params, batch) - loss) / loss}
    for part, blk, x, _got in fam.halves_at(params, hs):
        for b in range(batch.shape[0]):
            with reference.highest():
                got, _ = low._half[part](blk, x[b])
            err, gap = fam.ref.half_error(part, blk, x[b], got)
            if part == "moe":
                err = jnp.where(gap >= fam.tie_gap, err, 0.0)
            worst[part] = max(worst.get(part, 0.0), float(err.max()))
    got = fam._sampled(low.loss_and_grads(params, batch, at=hs)[1])
    errs = {**worst, **{"grad." + k: family._rel(got[k], want[k])
                        for k in want}}
    return {"errors": errs, "fails": sorted(
        k for k, v in errs.items()
        if not v <= family.LIMITS.get(k, family.LIMITS["grad"]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.load_cell(CELL, rehearse=args.rehearse_cpu)
    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as np

    with tempfile.TemporaryDirectory() as workdir:
        fam = family.Family(cell.config, cell.traffic, args.seed, workdir,
                            False, lambda name: contextlib.nullcontext())
        fam.make_inputs()
        fam.build_model()
        batch = fam.sequences[:fam.seqs]
        fam.rows_seen = np.unique(batch)[:family.ROW_SAMPLE]
        fam.rows_unseen = np.setdiff1d(
            np.arange(fam.vocab), batch)[:family.ROW_SAMPLE]
        hs = fam._hidden(batch)
        fam.sample_expert = fam.fullest_expert(fam.state.params, batch, hs)
        fam.state.opt_state = None     # 5.3 GB the readings do not need
        loss = fam.ref.loss(fam.state.params, batch)
        want = fam._sampled(
            fam.ref.loss_and_grads(fam.state.params, batch, at=hs)[1])
        out = {"device": jax.devices()[0].device_kind,
               "limits": family.LIMITS}
        for name, lower in READINGS.items():
            out["reference_with_" + name] = lowered_reading(
                fam, batch, hs, want, loss,
                {k: jnp.dtype(v) for k, v in lower.items()})
            jax.clear_caches()         # the lowered programs' reservations
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
