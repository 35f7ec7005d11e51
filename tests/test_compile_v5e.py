"""Real-size programs compiled for a v5e chip that is described, not
attached (on-chip-measurement guide, section 2): costs no chip time, says
nothing about results or times.

One file for every such test: only one process may load the TPU's
compiler, and the topology is described inside a fixture so that every
worker collects the same tests and only the one given this file loads it.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _cell(name):
    """(configuration, traffic) of a cell of BENCHMARK.json, as data."""
    def load(*path):
        with open(os.path.join(REPO, *path)) as f:
            return json.load(f)
    bench = load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (load(config["file"]),
            load("benchmark", "traffic", cell["traffic"] + ".json"))


def test_cbow2m_demo_step_writes_rows_back_in_place(topo, no_compile_cache,
                                                    tmp_path, monkeypatch):
    """The ``cbow2m-demo`` train step at 2,340,001 x 300 on one v5e chip:
    the four fields are updated in place, the push's write-back adds no
    whole-field temporary and no layout copy to the parent's (PR 29's
    step: 3.37 GiB of temporaries = one padded row-major field, 11
    copies), and its ~5,000-row pushes take the per-row form."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from swiftmpi_tpu.cluster.cluster import Cluster
    from swiftmpi_tpu.models.word2vec import Word2Vec
    from swiftmpi_tpu.parameter import sparse_table
    from swiftmpi_tpu.utils import global_config

    config, traffic = _cell("cbow2m-demo")
    w2v = config["word2vec"]
    vocab, width, window = (int(config["vocab_size"]), int(w2v["len_vec"]),
                            int(w2v["window"]))
    minibatch = int(traffic["minibatch"])
    centers = max(256, minibatch // (2 * window))
    conf = tmp_path / "cell.conf"
    conf.write_text("\n".join(
        ["[word2vec]", *(f"{k}: {v}" for k, v in w2v.items()),
         "[server]", *(f"{k}: {v}" for k, v in config["server"].items()),
         "[worker]", f"minibatch: {minibatch}"]) + "\n")
    global_config().load_conf(str(conf)).parse()

    # no array can be placed on a chip that is not there: shapes only
    def shapes_only(self):
        return {n: jax.ShapeDtypeStruct((self.key_index.capacity, fs.dim),
                                        fs.dtype,
                                        sharding=self.row_sharding())
                for n, fs in self.access.fields.items()}

    monkeypatch.setattr(sparse_table.SparseTable, "_init_state", shapes_only)
    cluster = Cluster(global_config(),
                      devices=list(topo.devices)[:1]).initialize()
    model = Word2Vec(cluster=cluster)
    # Word2Vec.build_from_vocab's capacity rule
    capacity = max(64, int(vocab * 1.3 / cluster.n_servers) + 1)
    model.table = cluster.create_table("w2v", model.access, capacity)
    step = model._build_step()
    rep = NamedSharding(cluster.mesh, P())

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=rep)

    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = step.lower(
        model.table.state, shape((vocab,), jnp.int32),
        shape((vocab,), jnp.float32), shape((vocab,), jnp.int32),
        shape((centers,), jnp.int32), shape((centers, 2 * window), jnp.int32),
        shape((centers, 2 * window), jnp.bool_),
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()

    assert (capacity, width) == (2_340_001, 300)
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert {(int(o), int(i)) for o, i in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", aliased)} >= {(i, i) for i in range(4)}
    assert mem.temp_size_in_bytes <= 3.37 * GIB * 1.05
    field = rf"f32\[{capacity},{width}\]"
    assert len(re.findall(rf"= {field}\S* copy\(", text)) <= 11
    assert cluster.transfer.resolved_write_back == dict.fromkeys(
        ("h", "h2sum", "v", "v2sum"), "per_row")
    scatters = re.findall(rf"= {field}\S* scatter\(.*apply/scatter", text)
    assert len(scatters) == 4
    assert not any("indices_are_sorted=true" in s for s in scatters)
