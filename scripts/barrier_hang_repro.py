"""Reproduction on the chip: ``cbow2m-demo``'s train step HANGS a TPU v5e
when its two ``tiles`` pushes (8.4 and 1.2 MB of gradients) are ordered
behind the state they are given by an ``optimization_barrier`` over the
table's four 3.59 GB fields and the gradients — the barrier
``transfer/xla.py::_push_rows`` puts before a push of
``_ORDERED_PUSH_BYTES`` or more, where it holds the step's peak memory and
runs (PERF.md section 6, PR 47; ROADMAP D10).  Not understood: an open
safety item.  What this script found on a v5e (PR 47's review): the
barrier over the gradients alone runs, over the fields alone runs, over
both in one — what ties the batch's sums to the state — hangs.

Every variant runs the cell through ``benchmark/run.py`` in a child
process of its own — the parent never touches JAX, a chip belongs to one
process — and is killed ``--timeout`` seconds in: a step that hangs
returns nothing.

    python scripts/barrier_hang_repro.py                 # on the chip
    python scripts/barrier_hang_repro.py --variants all --timeout 150

Variants (what the barrier before EVERY ``tiles`` push is over):

``committed``  the tree as it is: no barrier under 16 MiB (the control)
``all``        every field of the state and the gradients (the hang)
``state``      the fields alone
``grads``      the gradients alone

Prints one JSON line a variant, ``{"variant", "hung", "rc", "seconds",
"words_per_s"}``, and writes them to
``chiprun_out/barrier_hang_repro.json``.
"""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VARIANTS = ("committed", "grads", "state", "all")


def child(variant: str, seed: int, rehearse: bool) -> None:
    sys.path.insert(0, ROOT)
    import runpy

    import jax

    from swiftmpi_tpu.transfer import xla

    if variant != "committed":
        xla._ORDERED_PUSH_BYTES = 0
        real = jax.lax.optimization_barrier

        def barrier(operands):
            state, grads = operands
            if variant == "state":
                return real(state), grads
            if variant == "grads":
                return state, real(grads)
            return real(operands)
        # `_push_rows` calls it through the module it imported
        xla.jax.lax.optimization_barrier = barrier
    sys.argv = ["benchmark/run.py", "--workload", "cbow2m-demo", "--seed",
                str(seed), "--seconds", "2", "--trace", "0",
                *(["--rehearse-cpu"] if rehearse else [])]
    runpy.run_path(os.path.join(ROOT, "benchmark", "run.py"),
                   run_name="__main__")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--variants", nargs="+", default=list(VARIANTS),
                    choices=VARIANTS)
    ap.add_argument("--timeout", type=float, default=150.0)
    ap.add_argument("--seed", type=int, default=3000015857)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="no chip: the harness's CPU rehearsal at toy size "
                    "(the script's own plumbing; nothing hangs there)")
    ap.add_argument("--child", choices=VARIANTS, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        child(args.child, args.seed, args.rehearse_cpu)
        return 0
    out = []
    for variant in args.variants:
        t0 = time.perf_counter()
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child", variant,
             "--seed", str(args.seed),
             *(["--rehearse-cpu"] if args.rehearse_cpu else [])],
            cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True)
        try:
            stdout, _ = proc.communicate(timeout=args.timeout)
            hung = False
        except subprocess.TimeoutExpired:
            proc.kill()
            stdout, _ = proc.communicate()
            hung = True
        line = {}
        for text in reversed(stdout.splitlines()):
            if text.startswith("{"):
                line = json.loads(text)
                break
        rate = line.get("metrics", {}).get("words_per_s", {}).get("value")
        out.append({"variant": variant, "hung": hung, "rc": proc.returncode,
                    "seconds": round(time.perf_counter() - t0, 1),
                    "words_per_s": rate})
        print(json.dumps(out[-1]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "barrier_hang_repro.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
