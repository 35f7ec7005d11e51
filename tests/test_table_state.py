"""The table's state across the calls the benchmark's harness makes
(``Word2Vec.build_from_vocab`` -> ``train()`` -> ``train()``), and across
restore, growth and arming: every array stays where the table says it
lives (`SparseTable.field_sharding`) and in the device format it had, so
the step is compiled once and no call lays a field out again; and the
STORED row (`access.stored_width`: 100 -> 128 lanes here, 300 -> 384 in
the benchmark's cells) keeps zeros beyond the vector through training,
while everything that leaves the table is cut to the vector.

On the CPU backend every layout is the default one, so what is held here
is placement, widths and the lowering count;
``tests/test_compile_v5e.py`` holds what the chip's compiler does with a
384-wide field (PERF.md section 6, PR 32).
"""

import jax
import jax.monitoring
import numpy as np
import pytest

from swiftmpi_tpu.cluster.cluster import Cluster
from swiftmpi_tpu.data.text import CBOWBatcher, build_vocab, synthetic_corpus
from swiftmpi_tpu.io.checkpoint import load_checkpoint, save_checkpoint
from swiftmpi_tpu.models.word2vec import Word2Vec
from swiftmpi_tpu.parameter.access import (AccessMethod, FieldSpec,
                                           stored_width)
from swiftmpi_tpu.parameter.key_index import HotColdPartition
from swiftmpi_tpu.parameter.sparse_table import (ROWVER_KEY, ef_name,
                                                 hot_name)
from swiftmpi_tpu.utils import ConfigParser

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def assert_placed(table):
    for name, arr in table.state.items():
        assert arr.sharding.is_equivalent_to(table.field_sharding(name),
                                             arr.ndim), name


class Lowerings:
    """Names of the programs lowered while active, from JAX's own
    monitoring events (as ``benchmark/lib/loop.py::CompileCounter``)."""

    def __enter__(self):
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, _secs, fun_name="", **_kw):
        if event == LOWERING:
            self.names.append(str(fun_name))

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


class _Access(AccessMethod):
    fields = {"w300": FieldSpec(300), "w1": FieldSpec(1)}


@pytest.mark.parametrize("name, replicated", [
    ("w300", False), ("w1", False), (ROWVER_KEY, False),
    (ef_name("w300"), False), (hot_name("w300"), True)])
def test_every_plane_is_built_where_the_table_says(devices8, name,
                                                   replicated):
    cfg = ConfigParser().update({"cluster": {"transfer": "xla"}})
    cluster = Cluster(cfg, devices=devices8).initialize()
    table = cluster.create_table(
        "t", _Access(), 16,
        partition=HotColdPartition(np.arange(1, 5, dtype=np.uint64)))
    table.ensure_ef(["w300"])
    table.ensure_row_versions()
    want = (table.replicated_sharding() if replicated
            else table.row_sharding())
    assert table.field_sharding(name) == want
    assert table.state[name].sharding.is_equivalent_to(
        want, table.state[name].ndim)


def _model(devices, sg=0, worker=None):
    cfg = ConfigParser().update({
        "cluster": {"transfer": "xla"},
        "word2vec": {"len_vec": 100, "window": 3, "negative": 2, "sg": sg,
                     "sample": -1, "learning_rate": 0.05},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 192, **(worker or {})},
    })
    corpus = synthetic_corpus(40, vocab_size=60, length=12, seed=4)
    cluster = Cluster(cfg, devices=devices).initialize()
    model = Word2Vec(config=cfg, cluster=cluster, seed=5)
    model.build_from_vocab(build_vocab(corpus))
    return model, CBOWBatcher(corpus, model.vocab, model.window)


@pytest.mark.parametrize("worker", [{}, {"inner_steps": 3},
                                    {"local_steps": 2}],
                         ids=["single", "fused", "stale"])
@pytest.mark.parametrize("sg", [0, 1], ids=["cbow", "sg"])
def test_train_returns_the_state_it_was_compiled_for(devices8, sg, worker):
    """``build_from_vocab`` -> two ``train()`` calls: every array is where
    the table says and in the format it had before, and the second call
    lowers nothing — the state a step returns is the argument the step
    was compiled for."""
    model, batcher = _model(devices8, sg, worker)
    table = model.table
    assert_placed(table)
    before = {f: a.format for f, a in table.state.items()}

    with Lowerings() as first:
        model.train(batcher=batcher, niters=1, batch_size=32)
    assert any("step" in n or "multi" in n or "apply" in n
               for n in first.names), first.names
    assert_placed(table)
    assert {f: a.format for f, a in table.state.items()} == before

    with Lowerings() as second:
        model.train(batcher=batcher, niters=1, batch_size=32)
    assert second.names == []
    assert_placed(table)
    assert {f: a.format for f, a in table.state.items()} == before


def test_restore_growth_and_armed_planes_keep_their_place(devices8,
                                                          tmp_path):
    model, batcher = _model(devices8)
    model.train(batcher=batcher, niters=1, batch_size=32)
    table = model.table
    table.ensure_ef(["h", "v"])
    table.ensure_row_versions()
    assert_placed(table)
    path = str(tmp_path / "ckpt")
    save_checkpoint(table, path)
    want = {f: np.asarray(a) for f, a in table.state.items()}

    other, _ = _model(devices8)
    other.table.ensure_ef(["h", "v"])
    other.table.ensure_row_versions()
    load_checkpoint(other.table, path)
    assert_placed(other.table)
    for f, a in other.table.state.items():
        np.testing.assert_array_equal(np.asarray(a), want[f])

    other.table.grow()
    assert_placed(other.table)


# -- the stored row ----------------------------------------------------------

@pytest.mark.parametrize("width, stored", [
    (300, 384), (100, 128), (96, 128), (200, 256),    # <= a third more
    (128, 128), (384, 384),                           # nothing to pad
    (1, 1), (8, 8), (64, 64), (95, 95), (150, 150)])  # would pad more
def test_stored_width(width, stored):
    assert stored_width(width) == stored


@pytest.mark.parametrize("sg", [0, 1], ids=["cbow", "sg"])
def test_padding_lanes_stay_zero_and_nothing_wider_leaves(devices8, sg,
                                                          tmp_path):
    model, batcher = _model(devices8, sg)
    assert (model.len_vec, model.row_width) == (100, 128)
    for _ in range(2):
        model.train(batcher=batcher, niters=1, batch_size=32)
    table = model.table
    for f, a in table.state.items():
        a = np.asarray(a)
        assert a.shape == (table.capacity, 128), f
        assert not a[:, 100:].any(), f
        assert a[:, :100].any(), f
    key = int(model.vocab.keys[0])
    assert model.embedding(key).shape == (100,)
    assert table.unified_rows_host("v").shape == (table.capacity, 100)

    # the reference's text format carries the vector, not the stored row
    path = str(tmp_path / "vectors.txt")
    n = model.save(path)
    with open(path) as f:
        assert len(f.readline().split()) == 1 + 2 * 100
    other, _ = _model(devices8, sg)
    assert other.load(path) == n
    slots = other.table.key_index.lookup(model.vocab.keys)
    for f in ("v", "h"):
        np.testing.assert_allclose(
            other.table.unified_rows_host(f)[slots],
            table.unified_rows_host(f)[table.key_index.lookup(
                model.vocab.keys)], rtol=1e-6)
        assert not np.asarray(other.table.state[f])[:, 100:].any()


def test_a_checkpoint_of_narrow_rows_restores_into_the_stored_width(
        devices8, tmp_path):
    """A checkpoint written when a row was stored as wide as the vector
    loads into the wider row, zeros beyond."""
    model, batcher = _model(devices8)
    model.train(batcher=batcher, niters=1, batch_size=32)
    table = model.table
    path = str(tmp_path / "ckpt")
    save_checkpoint(table, path)
    with np.load(path + ".npz") as z:
        old = {k: (z[k][:, :100] if k.startswith("field__") else z[k])
               for k in z.files if not k.startswith("__crc__")}
    np.savez(path + ".npz", **old)
    other, _ = _model(devices8)
    load_checkpoint(other.table, path, verify=False)
    for f, a in other.table.state.items():
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(table.state[f]))


def test_glove_rows_are_stored_wide_and_exported_narrow(devices8):
    from swiftmpi_tpu.models.glove import GloVe

    cfg = ConfigParser().update({
        "cluster": {"transfer": "xla"},
        "glove": {"len_vec": 100, "window": 3, "learning_rate": 0.05,
                  "minibatch": 128},
    })
    model = GloVe(config=cfg, cluster=Cluster(cfg, devices=devices8)
                  .initialize())
    losses = model.train(synthetic_corpus(40, vocab_size=30, length=12,
                                          seed=2), niters=2)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    for f in ("w", "wt", "w2sum", "wt2sum"):
        a = np.asarray(model.table.state[f])
        assert a.shape[1] == 128 and a[:, :100].any()
        assert not a[:, 100:].any(), f
    assert np.asarray(model.table.state["b"]).shape[1] == 1
    assert model.embedding_index().vecs.shape[1] == 100
