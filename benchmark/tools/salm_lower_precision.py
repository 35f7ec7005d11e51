#!/usr/bin/env python3
"""The second of the two readings a limit of the sparse-attention cell's
first-step comparison lies between, on the chip at the timed sizes
(``tools/lm_lower_precision.py``'s method):

    python3 benchmark/tools/salm_lower_precision.py [--seed N] [--variants a,b] [--rehearse-cpu]

The first reading is what the program (bf16 operands, float32 index sums,
statistics, loss and an exact top 2,048, as the configuration states) gives
against the plain reference: ``Family.first_step_check``'s own numbers,
printed by every run of the cell.  This tool gives the second: what the
*reference* gives when it is computed lower, against the true reference —

* ``float8_e4m3fn`` operands of the large products (the nearest precision
  below the stated bf16), and ``bfloat16`` operands, which should land near
  the first reading;
* ``bfloat16`` softmax statistics of the attention (the configuration
  states float32);
* a ``bfloat16`` head softmax and loss (it states float32);
* a ``bfloat16`` sum of the index scores' sixteen terms (it states float32);
* an approximate top-k (``lax.approx_max_k`` at a recall of 0.95, its
  default, and of 0.5) where the model's is exact;

and two faults of the objective that no precision makes: the next-token loss
averaged over the first half of the sequence's targets alone, and the index
loss entering the objective at half its weight.

Readings per variant, under the names ``families/salm.py::verdict`` holds
to ``LIMITS`` (or ``check.index_tie_gap``) — the same function decides here
which limits a variant ``fails``:

* on the program's own half-layer inputs of the seed's check batch: the
  index scores, the picks that differ from the reference's and their
  distance from the threshold (sampled query blocks of every layer), then —
  each under the variant's own selection, the reference given the same — the
  per-token update of the sparse half and of the expert half and the layer's
  index loss, the worst over the layers;
* on the timed first step's batch, under the program's own selections of it
  (so not for an approximate selection, which these cannot see): the
  objective (``loss``), its index part (``step.index_loss``) and, for the
  lowered operands, every sampled tensor's clipped gradient (``grad.*``).

Prints one JSON line.  Exits 1 if a variant of ``MUST_FAIL`` passes every
limit or ``bfloat16_operands``, the stated precision, fails one
(``wrongly_decided``; a rehearsal at toy size, where the limits do not
apply, exits 0).  A chip run of its own.
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

from benchmark.families import salm as family      # noqa: E402
from benchmark.lib import spec                     # noqa: E402
from benchmark.reference import salm as reference  # noqa: E402

CELL = "keye2-ep8-16k-t16k"
#: variant -> (what it lays over the reference's dims, whether the first
#: step's gradients are read under it)
VARIANTS = {
    "bfloat16_operands": ({"operands": "bfloat16"}, True),
    "float8_e4m3fn_operands": ({"operands": "float8_e4m3fn"}, True),
    "bfloat16_statistics": ({"statistics": "bfloat16"}, False),
    "bfloat16_loss": ({"loss_dtype": "bfloat16"}, False),
    "bfloat16_index_sum": ({"index_sum": "bfloat16"}, False),
    "approx_topk_recall_0.95": ({"approx_topk": 0.95}, False),
    "approx_topk_recall_0.5": ({"approx_topk": 0.5}, False)}
FAULTS = ("fault_half_sequence_loss", "fault_half_index_loss_weight")
#: what the first-step comparison has to refuse.  Not among them, for what
#: the chip read (PERF.md section 6, PR 49): bf16 attention statistics and a
#: bf16 index sum, whose readings on untrained weights lie inside the stated
#: precision's own (``tests/test_sparse_attention.py`` holds them on the
#: CPU), and ``approx_max_k`` at its default recall, which at k = S / 8 picks
#: the exact top k on this chip (0 picks differ)
MUST_FAIL = ("float8_e4m3fn_operands", "bfloat16_loss",
             "approx_topk_recall_0.5", *FAULTS)


def lowered_reading(fam, hs, low) -> dict:
    """Errors of the reference ``low`` against the true reference on the
    program's half-layer inputs ``hs`` of sequence 0."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng([fam.seed, 0xF4EE])
    rows = min(family.INDEX_ROWS, fam.seq_len)
    worst = {}

    def note(name, value):
        worst[name] = max(worst.get(name, 0.0), float(value))

    for layer, blk in enumerate(fam._sparse_blocks(fam.state.params)):
        x, mid = (jnp.asarray(hs[2 * layer + i][0]) for i in (0, 1))
        for row0 in fam.sampled_blocks(rng):
            got = low.index_block(blk, x, row0, rows)
            want = fam.ref.index_block(blk, x, row0, rows)
            for name, v in family.index_errors(*got, *want).items():
                note(name, v)
        keep = low.selection(blk, x)
        with reference.highest():
            got, _gap, li = low._half["sparse"](blk, x, keep)
        err, _gap, li_ref = fam.ref.half_error("sparse", blk, x, got, keep)
        note("sparse", err.max())
        note("index_loss", abs(float(li) - float(li_ref)) / float(li_ref))
        with reference.highest():
            got, _gap, _li = low._half["moe"](blk, mid, None)
        err, gap, _li = fam.ref.half_error("moe", blk, mid, got)
        note("moe", jnp.where(gap >= fam.tie_gap, err, 0.0).max())
    return worst


def step_reading(fam, low, step, with_grads: bool) -> dict:
    """Errors of the reference ``low`` against the true reference on the
    timed first step's batch under the program's selections (``step``:
    :func:`first_step`'s)."""
    params = fam.state.params
    got, want = low.losses(params, step["batch"], step["keeps"]), step["losses"]
    out = {"loss": abs(got[0] - want[0]) / want[0],
           "step.index_loss": abs(got[2] - want[2]) / want[2]}
    if with_grads:
        grads = fam.clipped(low.loss_and_grads(
            params, step["batch"], at=step["hs"], keeps=step["keeps"])[1])
        out.update({"grad." + k: family._rel(grads[k], g)
                    for k, g in step["grads"].items()})
    return out


def first_step(fam) -> dict:
    """The timed first step's batch, the program's half-layer inputs and
    selections of it, and the true reference's losses and sampled clipped
    gradients there: what ``Family.first_step_check`` holds the step to."""
    import numpy as np

    rng = np.random.default_rng([fam.seed, 0xF4EE])
    batch, hs, keeps = fam.first_batch(rng)
    params = fam.state.params
    return {"batch": batch, "hs": hs, "keeps": keeps,
            "losses": fam.ref.losses(params, batch, keeps),
            "grads": fam.clipped(fam.ref.loss_and_grads(
                params, batch, at=hs, keeps=keeps)[1])}


def fault_readings(fam, step) -> dict:
    """``loss`` under the two faults of the objective, from the true
    reference's parts: the next-token loss as the mean over the first half
    of the targets (on the program's own last hidden state), and the index
    loss at half its weight."""
    import jax.numpy as jnp

    params = fam.state.params
    total, _main, index = step["losses"]
    S, errs = fam.seq_len, []
    with reference.highest():
        for b, seq in enumerate(step["batch"]):
            x, t = jnp.asarray(step["hs"][-1][b]), jnp.asarray(seq)
            nll = lambda n: float(fam.ref._nll(
                params["head"], params["ln_f"], x[:n], t[:n])) / (n - 1)
            errs.append(abs(nll(S // 2 + 1) - nll(S)))
    return {FAULTS[0]: {"loss": max(errs) / total},
            FAULTS[1]: {"loss": 0.5 * index / total}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--variants", default="",
                    help="comma-separated names; default: all")
    args = ap.parse_args(argv)
    cell = spec.load_cell(CELL, rehearse=args.rehearse_cpu)
    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp

    wanted = args.variants.split(",") if args.variants else [*VARIANTS,
                                                             *FAULTS]
    with tempfile.TemporaryDirectory() as workdir:
        fam = family.Family(cell.config, cell.traffic, args.seed, workdir,
                            False, lambda name: contextlib.nullcontext())
        fam.make_inputs()
        fam.build_model()
        hs = fam._hidden(fam.check_batch)
        fam.state.opt_state = None     # 3.7 GB the readings do not need
        step = first_step(fam)
        limits = {**family.LIMITS, "index_tie_gap": fam.index_tie_gap}
        out = {"device": jax.devices()[0].device_kind, "limits": limits}
        readings = {k: v for k, v in fault_readings(fam, step).items()
                    if k in wanted}
        for name, (how, with_grads) in VARIANTS.items():
            if name not in wanted:
                continue
            low = reference.Reference({**fam.dims, **{
                k: jnp.dtype(v) if isinstance(v, str) else v
                for k, v in how.items()}})
            readings[name] = lowered_reading(fam, hs, low)
            if "approx_topk" not in how:
                readings[name].update(step_reading(fam, low, step,
                                                   with_grads))
            jax.clear_caches()         # the variant's programs' reservations
    passed = []
    for name, errs in readings.items():
        fails = sorted(k for k, f in family.verdict(errs, limits).items()
                       if not f["ok"])
        out["reference_with_" + name] = {"errors": errs, "fails": fails}
        if name in MUST_FAIL and not fails:
            passed.append(name)
    if readings.get("bfloat16_operands") and \
            out["reference_with_bfloat16_operands"]["fails"]:
        passed.append("bfloat16_operands fails")
    out["wrongly_decided"] = passed
    print(json.dumps(out))
    return 1 if passed and not args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
