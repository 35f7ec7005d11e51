#!/usr/bin/env python3
"""Compile a cell's real-size train step for a v5e chip that is described,
not attached, and print what the chip's compiler says it needs.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_real_size.py <cell> \
        [--vocab N] [--hlo FILE]

Costs no chip time (on-chip-measurement guide, section 2): a table that does
not fit, or a step whose working set does not fit beside it, is refused here
with the compiler's own memory report.  Nothing runs, so this says nothing
about results or times, and a compile that passes is not a chip run.

The model is built through the program's own classes on the described
devices; only ``SparseTable._init_state`` is replaced by shapes, because no
array can be placed on a device that is not there.  Everything the model
would derive from the vocabulary (alias tables, slots) enters as shapes.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import tempfile
import time
from collections import Counter

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.families.w2v import Family  # noqa: E402
from benchmark.lib import spec             # noqa: E402

COLLECTIVE = (r"\b(all-to-all|all-reduce|all-gather|reduce-scatter|"
              r"collective-permute)[-\w.]* = ")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--vocab", type=int, default=None,
                    help="try another vocabulary size than the config's")
    ap.add_argument("--hlo", default=None, help="write the compiled HLO here")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from swiftmpi_tpu.cluster.cluster import Cluster
    from swiftmpi_tpu.models.word2vec import Word2Vec
    from swiftmpi_tpu.parameter import sparse_table
    from swiftmpi_tpu.utils import global_config, reset_global_config

    cell = spec.load_cell(args.cell)
    if cell.family != "w2v":
        raise SystemExit(f"family {cell.family!r}: only w2v is wired here")
    vocab = args.vocab or int(cell.config["vocab_size"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices)[:cell.chips]

    def shapes_only(self):
        return {n: jax.ShapeDtypeStruct((self.key_index.capacity, fs.dim),
                                        fs.dtype,
                                        sharding=self.row_sharding())
                for n, fs in self.access.fields.items()}

    sparse_table.SparseTable._init_state = shapes_only
    with tempfile.TemporaryDirectory() as workdir:
        fam = Family(cell.config, cell.traffic, 0, workdir, False, None)
        reset_global_config()
        global_config().load_conf(fam.write_conf()).parse()
    cluster = Cluster(global_config(), devices=devices).initialize()
    model = Word2Vec(cluster=cluster)
    # Word2Vec.build_from_vocab's capacity rule
    cap = max(64, int(vocab * 1.3 / cluster.n_servers) + 1)
    model.table = cluster.create_table("w2v", model.access, cap)
    step = model._build_step()

    rep = NamedSharding(cluster.mesh, P())

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=rep)

    B, W2 = fam.centers, 2 * fam.window
    key = jax.eval_shape(lambda: jax.random.key(0))
    t0 = time.time()
    compiled = step.lower(
        model.table.state, shape((vocab,), jnp.int32),
        shape((vocab,), jnp.float32), shape((vocab,), jnp.int32),
        shape((B,), jnp.int32), shape((B, W2), jnp.int32),
        shape((B, W2), jnp.bool_),
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print(f"{args.cell}: vocab {vocab}, {model.table.capacity} rows x "
          f"{fam.len_vec}, {B} centers a step, {cell.chips} chip(s), mesh "
          f"{dict(cluster.mesh.shape)}, rendering "
          f"{model.resolved_rendering}; compiled in {time.time() - t0:.0f}s")
    for name in ("argument", "output", "alias", "temp", "generated_code"):
        print(f"  {name + '_size':22s}"
              f"{getattr(mem, name + '_size_in_bytes') / 2**30:8.2f} GiB "
              "a chip")
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"  arguments + outputs - aliased + temporaries = "
          f"{total / 2**30:.2f} GiB of a v5e chip's 15.75 GiB")
    print("  collectives:", dict(Counter(re.findall(COLLECTIVE, text))))
    table_rows = model.table.capacity // cluster.n_servers
    copies = re.findall(rf"= f32\[{table_rows},{fam.len_vec}\]\S* copy\(",
                        text)
    print(f"  whole-field copies (f32[{table_rows},{fam.len_vec}]) in the "
          f"step: {len(copies)}")
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
