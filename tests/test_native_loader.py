"""Parity tests: native C++ loader vs the pure-Python pipeline."""

import numpy as np
import pytest

from swiftmpi_tpu.data.text import (CBOWBatcher, build_vocab, load_corpus,
                                    synthetic_corpus)
from swiftmpi_tpu.data import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native loader not built")


@pytest.fixture
def corpus_file(tmp_path):
    corpus = synthetic_corpus(30, vocab_size=80, length=20, seed=12)
    p = tmp_path / "corpus.txt"
    with open(p, "w") as f:
        for s in corpus:
            f.write(" ".join(map(str, s)) + "\n")
    return str(p), corpus


def test_native_vocab_matches_python(corpus_file):
    path, corpus = corpus_file
    vocab_py = build_vocab(load_corpus(path))
    vocab_c, tokens, offsets = native.load_corpus_native(path)
    np.testing.assert_array_equal(vocab_py.keys, vocab_c.keys)
    np.testing.assert_array_equal(vocab_py.counts, vocab_c.counts)
    assert len(offsets) - 1 == len(corpus)
    assert tokens.sum() >= 0 and (tokens < len(vocab_c)).all()


def test_native_bkdr_mode_matches_python(tmp_path):
    p = tmp_path / "words.txt"
    p.write_text("the quick brown fox the the quick\n")
    vocab_py = build_vocab(load_corpus(str(p), mode="bkdr"))
    vocab_c, _, _ = native.load_corpus_native(str(p), mode="bkdr")
    np.testing.assert_array_equal(vocab_py.keys, vocab_c.keys)
    np.testing.assert_array_equal(vocab_py.counts, vocab_c.counts)


def test_native_vocab_parity_with_sentence_filtering(tmp_path):
    # Vocab counting must see the same filtered token stream as the corpus
    # map (and as python's load_corpus -> build_vocab pipeline).
    p = tmp_path / "c.txt"
    p.write_text("1 2\n3 4 5 6 7\n1 3 5 7 9 11\n")
    vocab_py = build_vocab(load_corpus(str(p), min_sentence_length=3))
    vocab_c, _, _ = native.load_corpus_native(str(p), min_sentence_length=3)
    np.testing.assert_array_equal(vocab_py.keys, vocab_c.keys)
    np.testing.assert_array_equal(vocab_py.counts, vocab_c.counts)


def test_native_vocab_parity_negative_tokens(tmp_path):
    p = tmp_path / "n.txt"
    p.write_text("-5 -5 3 3 3 -5 7\n")
    vocab_py = build_vocab(load_corpus(str(p)))
    vocab_c, _, _ = native.load_corpus_native(str(p))
    np.testing.assert_array_equal(vocab_py.keys, vocab_c.keys)
    # and the batcher path resolves raw negative tokens via index_of
    assert vocab_py.index_of(-5) is not None
    assert vocab_py.index_of(-5) == vocab_c.index_of(-5)


def test_native_min_sentence_and_chunking(tmp_path):
    p = tmp_path / "mixed.txt"
    p.write_text("1 2\n" + " ".join(str(i % 5) for i in range(70)) + "\n")
    vocab_c, tokens, offsets = native.load_corpus_native(
        str(p), min_sentence_length=3, max_sentence_length=30)
    lens = np.diff(offsets)
    # "1 2" dropped (len<3); 70-token line chunked 30/30/10
    assert lens.tolist() == [30, 30, 10]


def test_native_batcher_covers_all_positions(corpus_file):
    path, corpus = corpus_file
    vocab_c, tokens, offsets = native.load_corpus_native(path)
    b = native.NativeCBOWBatcher(tokens, offsets, vocab_c, window=3)
    centers = []
    for batch in b.epoch(64):
        assert batch.contexts.shape == (64, 6)
        # every real row has at least one context; padding is zero
        assert batch.ctx_mask[:batch.n_words].any(axis=1).all()
        assert (batch.contexts[~batch.ctx_mask] == 0).all()
        centers.append(batch.centers[:batch.n_words])
    centers = np.concatenate(centers)
    # without subsampling every position is a center exactly once per epoch
    got = np.bincount(centers, minlength=len(vocab_c))
    np.testing.assert_array_equal(got, np.asarray(vocab_c.counts))


def test_native_batcher_subsampling_and_reshuffle(corpus_file):
    path, _ = corpus_file
    vocab_c, tokens, offsets = native.load_corpus_native(path)
    b = native.NativeCBOWBatcher(tokens, offsets, vocab_c, window=2,
                                 sample=0.01, seed=7)
    n1 = sum(bt.n_words for bt in b.epoch(64))
    n2 = sum(bt.n_words for bt in b.epoch(64))
    total = int(vocab_c.counts.sum())
    assert 0 < n1 < total  # subsampling dropped centers
    assert 0 < n2 < total
    first_a = next(iter(b.epoch(64))).centers.copy()
    first_b = next(iter(b.epoch(64))).centers.copy()
    assert not np.array_equal(first_a, first_b)  # epochs reshuffled


def test_native_batcher_trains_word2vec(devices8, corpus_file):
    # End-to-end: the native batcher slots into Word2Vec.train unchanged.
    from swiftmpi_tpu.models import Word2Vec
    from swiftmpi_tpu.utils import ConfigParser
    path, corpus = corpus_file
    vocab_c, tokens, offsets = native.load_corpus_native(path)
    cfg = ConfigParser().update({
        "cluster": {"transfer": "xla"},
        "word2vec": {"len_vec": 8, "window": 2, "negative": 3,
                     "sample": -1, "learning_rate": 0.05},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 256},
    })
    model = Word2Vec(config=cfg)
    losses = model.train(load_corpus(path), niters=2, batch_size=64,
                         batcher=native.NativeCBOWBatcher(
                             tokens, offsets, vocab_c, window=2))
    assert len(losses) == 2


# ---- prefetch executor ----------------------------------------------------

def test_prefetcher_stream_matches_plain_batcher(corpus_file):
    """Same seed => the prefetching epoch yields the identical batch
    stream (FIFO queue preserves producer order)."""
    path, _ = corpus_file
    vocab_c, tokens, offsets = native.load_corpus_native(path)
    plain = native.NativeCBOWBatcher(tokens, offsets, vocab_c, window=2,
                                     seed=42)
    pre = native.PrefetchingCBOWBatcher(tokens, offsets, vocab_c, window=2,
                                        seed=42, depth=3)
    a = list(plain.epoch(64))
    b = list(pre.epoch(64))
    assert len(a) == len(b) and len(a) > 1
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.centers, y.centers)
        np.testing.assert_array_equal(x.contexts, y.contexts)
        np.testing.assert_array_equal(x.ctx_mask, y.ctx_mask)
        assert x.n_words == y.n_words


def test_prefetcher_early_abandon_no_hang(corpus_file):
    """Dropping the epoch iterator mid-stream must cancel the producer
    thread promptly (bounded queue would otherwise block it forever)."""
    path, _ = corpus_file
    vocab_c, tokens, offsets = native.load_corpus_native(path)
    pre = native.PrefetchingCBOWBatcher(tokens, offsets, vocab_c, window=2,
                                        depth=1)
    it = pre.epoch(16)
    next(it)
    it.close()  # triggers finally -> smtpu_prefetcher_free -> join
    # a fresh epoch still works after the abandoned one
    assert sum(b.n_words for b in pre.epoch(64)) > 0


# ---- native libSVM parser -------------------------------------------------

def test_native_libsvm_matches_python(tmp_path):
    from swiftmpi_tpu.data.libsvm import load_file, to_csr
    p = tmp_path / "a9a.txt"
    p.write_text(
        "+1 3:1 11:0.5 14:-2\n"
        "-1 1:2.5 7:1\n"
        "\n"
        "# a comment line\n"
        "1 5:1 # trailing comment 9:9\n"
        "-1 2:0.125\n")
    labels, offsets, ids, vals = native.parse_libsvm_native(str(p))
    csr = to_csr(load_file(str(p)))
    np.testing.assert_array_equal(labels, csr.labels)
    np.testing.assert_array_equal(offsets, csr.offsets)
    np.testing.assert_array_equal(ids, csr.feat_ids)
    np.testing.assert_allclose(vals, csr.feat_vals)
    assert labels.tolist() == [1.0, 0.0, 1.0, 0.0]


def test_native_libsvm_batches_match_python(tmp_path):
    from swiftmpi_tpu.data.libsvm import (iter_minibatches, load_data,
                                          load_file, synthetic_dataset)
    data = synthetic_dataset(37, dim=50, nnz=6, seed=3)
    p = tmp_path / "d.txt"
    with open(p, "w") as f:
        for y, feats in data:
            f.write(f"{int(y)} " +
                    " ".join(f"{k}:{v}" for k, v in feats) + "\n")
    csr = load_data(str(p))
    a = list(iter_minibatches(load_file(str(p)), 16))
    b = list(iter_minibatches(csr, 16))
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x.targets, y.targets)
        np.testing.assert_array_equal(x.feat_ids, y.feat_ids)
        np.testing.assert_allclose(x.feat_vals, y.feat_vals, rtol=1e-6)
        np.testing.assert_array_equal(x.mask, y.mask)


# ---- native text checkpoint IO --------------------------------------------

def test_native_text_dump_load_roundtrip(tmp_path, devices8):
    from swiftmpi_tpu.cluster import ps_mesh, SHARD_AXIS
    from swiftmpi_tpu.parameter import KeyIndex, SparseTable, w2v_access
    from swiftmpi_tpu.io.checkpoint import dump_table_text, load_table_text
    access = w2v_access(0.3, 8)
    ki = KeyIndex(1, 64)
    t = SparseTable(access, ki)
    keys = np.arange(10, 30, dtype=np.uint64)
    slots = ki.lookup(keys)
    # give rows distinguishable values
    import jax.numpy as jnp
    state = dict(t.state)
    v = np.asarray(state["v"]).copy()
    v[slots] = np.arange(20 * 8, dtype=np.float32).reshape(20, 8) / 7
    state["v"] = jnp.asarray(v)
    t.state = state
    path = str(tmp_path / "dump.txt")
    n = dump_table_text(t, path, fields=("v", "h"))
    assert n == 20
    # native writer layout: key TAB v-vec TAB h-vec
    parts = open(path).readline().split("\t")
    assert len(parts) == 3 and len(parts[1].split()) == 8

    t2 = SparseTable(access, KeyIndex(1, 64))
    n2 = load_table_text(t2, path, fields=("v", "h"))
    assert n2 == 20
    for k in (10, 17, 29):
        np.testing.assert_allclose(
            np.asarray(t2.state["v"])[t2.key_index.slot(k)],
            np.asarray(t.state["v"])[t.key_index.slot(k)], rtol=1e-6)


def test_native_and_python_text_dumps_parse_identically(tmp_path, devices8):
    """%.9g (native) and repr() (python) prints differ textually but must
    round-trip to the same float32 rows."""
    from swiftmpi_tpu.parameter import KeyIndex, SparseTable, lr_access
    from swiftmpi_tpu.io.checkpoint import (default_formatter,
                                            dump_table_text,
                                            load_table_text)
    access = lr_access(0.05)
    t = SparseTable(access, KeyIndex(1, 32), seed=5)
    t.key_index.lookup(np.arange(1, 9, dtype=np.uint64))
    p_native = str(tmp_path / "n.txt")
    p_python = str(tmp_path / "p.txt")
    dump_table_text(t, p_native, fields=("val",))
    dump_table_text(t, p_python, fields=("val",),
                    formatter=default_formatter(("val",)))
    t_n = SparseTable(access, KeyIndex(1, 32))
    t_p = SparseTable(access, KeyIndex(1, 32))
    load_table_text(t_n, p_native, fields=("val",))
    load_table_text(t_p, p_python, fields=("val",))
    for k in range(1, 9):
        np.testing.assert_array_equal(
            np.asarray(t_n.state["val"])[t_n.key_index.slot(k)],
            np.asarray(t_p.state["val"])[t_p.key_index.slot(k)])


def test_native_libsvm_edge_parity(tmp_path):
    """Feature-less rows dropped in both paths; malformed lines raise in
    both; empty-table dumps write an empty file."""
    from swiftmpi_tpu.data.libsvm import load_file, to_csr
    p = tmp_path / "edge.txt"
    p.write_text("1\n-1 2:0.5\n")  # label-only row must be dropped
    labels, offsets, ids, vals = native.parse_libsvm_native(str(p))
    csr_py = to_csr(load_file(str(p)))
    np.testing.assert_array_equal(labels, csr_py.labels)
    assert len(labels) == 1

    bad = tmp_path / "bad.txt"
    bad.write_text("1 abc 3:1\n")
    with pytest.raises(ValueError):
        native.parse_libsvm_native(str(bad))
    with pytest.raises(ValueError):
        load_file(str(bad))


def test_native_dump_empty_table(tmp_path, devices8):
    from swiftmpi_tpu.parameter import KeyIndex, SparseTable, lr_access
    from swiftmpi_tpu.io.checkpoint import dump_table_text
    t = SparseTable(lr_access(0.05), KeyIndex(1, 16))
    path = str(tmp_path / "empty.txt")
    assert dump_table_text(t, path, fields=("val",)) == 0
    assert open(path).read() == ""


def test_native_dump_reports_a_failed_write():
    """A write the OS refuses (here: /dev/full, ENOSPC) raises — it must
    not return the row count over a short file."""
    import errno
    import os
    if not os.path.exists("/dev/full"):
        pytest.skip("no /dev/full on this host")
    keys = np.arange(1, 5001, dtype=np.uint64)
    rows = np.ones((5000, 64), np.float32) / 3
    with pytest.raises(OSError) as e:
        native.dump_rows_native("/dev/full", keys, [rows])
    assert e.value.errno == errno.ENOSPC
