#!/usr/bin/env python3
"""An LM cell's real ``trainer_step`` compiled for a v5e chip that is
described, not attached (no chip time; says nothing about results or times):

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_lm_step.py <cell> [--text FILE]

Prints one JSON line: the parameter count, the compiler's memory report —
its own peak (``peak_memory_in_bytes``: what the chip must hold at once, and
the number to size a sequence by against the 15.75 GiB the runtime gives)
beside the sum arguments + outputs - aliased + temporaries, which counts
allocations that are never live together and so reads over 15.75 GiB for
steps the chip loads — and the ragged-dot kernel calls in the compiled text.
``--text`` writes ``as_text()`` there — for comparing a cell's step between
two trees: the same command in each, then ``diff`` (or the ``sha256`` this
prints).  Any family whose module has ``transformer_config(config, traffic)``
and ``trainer_kwargs``: ``lm``, ``bdlm``, ``swlm``, ``mlalm``, ``sslm`` and
``salm`` today.
"""

from __future__ import annotations

import argparse
import hashlib
import importlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import spec  # noqa: E402

GIB = 2 ** 30


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--text", default=None, metavar="FILE")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = spec.load_cell(args.cell)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from swiftmpi_tpu.models.trainer import Trainer

    # a compile for a described chip cannot be read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    family = importlib.import_module(f"benchmark.families.{cell.family}")
    cfg = family.transformer_config(cell.config, cell.traffic)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    trainer = Trainer(cfg, **family.trainer_kwargs(cell.config))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda k: trainer.init_state(k).tree(),
                       jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct(
        (int(cell.traffic["sequences_per_step"]),
         int(cell.traffic["sentence_tokens"])), jnp.int32, sharding=one)
    compiled = trainer._build_step().lower(
        state["params"], state["opt_state"], state["step"], tokens).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    if args.text:
        with open(args.text, "w") as f:
            f.write(text)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(json.dumps({
        "cell": cell.name,
        "parameters": int(sum(a.size
                              for a in jax.tree.leaves(state["params"]))),
        "arguments_gib": mem.argument_size_in_bytes / GIB,
        "temporaries_gib": mem.temp_size_in_bytes / GIB,
        "aliased_gib": mem.alias_size_in_bytes / GIB,
        "total_gib": total / GIB,
        "peak_gib": mem.peak_memory_in_bytes / GIB,
        "fits_15_75_gib": mem.peak_memory_in_bytes <= 15.75 * GIB,
        "ragged_dot_calls": len(re.findall(
            r'custom_call_target="tpu_custom_call".*?'
            r'op_name="ragged-dot-none"', text)),
        "instructions": text.count(" = "),
        "sha256": hashlib.sha256(text.encode()).hexdigest()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
