"""Positional-stencil rendering tests: wire format, working-set bound,
Python/native batcher parity, the sort-free span push, and golden checks
against both the numpy oracle and the reference-parity gather rendering.

The stencil contract (data/text.py StencilBatch): a batch is a stream
span of ``S = span_positions(B, W, keep_mean)`` positions (``B + 2W``
rounded up to lane tiles without subsampling; enough for ``B`` centers
under the center gate with it) plus per-center positions into it, and
``stencil_to_cbow`` expansion reproduces the per-pair batcher's stream
element for element at the same seed.  The device side
(models/word2vec.py ``_build_grads_stencil``) pulls, window-sums and
pushes each span position once, by dense shifted sums, and must match
the per-pair math bit-tight.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from swiftmpi_tpu.data import native  # noqa: E402
from swiftmpi_tpu.data.text import (CBOWBatcher, Vocab,  # noqa: E402
                                    build_vocab, load_corpus,
                                    span_positions, stencil_to_cbow,
                                    synthetic_corpus, unpack_span)
from swiftmpi_tpu.models.word2vec import Word2Vec, _Tally  # noqa: E402
from swiftmpi_tpu.ops.sampling import sample_alias  # noqa: E402
from swiftmpi_tpu.testing import cbow_batch_grads  # noqa: E402
from swiftmpi_tpu.utils import ConfigParser  # noqa: E402


def make_model(stencil=1, **overrides):
    cfg = ConfigParser().update({
        "cluster": {"server_num": 2, "transfer": "xla"},
        "word2vec": {"len_vec": 16, "window": 2, "negative": 5,
                     "sample": -1, "learning_rate": 0.05,
                     "min_sentence_length": 2, "stencil": stencil},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 512},
    })
    for sec, kv in overrides.items():
        for k, v in kv.items():
            cfg.set(sec, k, v)
    return Word2Vec(config=cfg)


def build_per_pair(model, sents):
    """``model.build(sents)`` held to the per-pair rendering: a CBOW
    model resolves to spans at build time, and a test that drives the
    step builders itself (no ``train()``, no batcher to settle it
    against) says here which one it wants."""
    model.build(sents)
    model.stencil = 0
    return model


def span_args(batch):
    """What the span step takes of a batch: the packed buffer, and the
    centers it is cut by."""
    return (jnp.asarray(batch.pack()),), {"centers": len(batch.center_pos)}


def corpus(n_sent=40, vocab=30, length=12, seed=0):
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, vocab + 1)
    p /= p.sum()
    return [list(map(int, rng.choice(np.arange(1, vocab + 1), size=length,
                                     p=p)))
            for _ in range(n_sent)]


def _pair_stream(batches):
    """Canonical (center, context-tuple) stream from CBOW batches."""
    out = []
    for b in batches:
        for i in range(b.n_words):
            out.append((int(b.centers[i]),
                        tuple(b.contexts[i][b.ctx_mask[i]].tolist())))
    return out


# -- wire format -----------------------------------------------------------


@pytest.mark.parametrize("sample", [-1.0, 1e-3])
def test_stencil_stream_matches_pair_stream(sample):
    """Same corpus + same seed: the expanded stencil stream equals the
    per-pair batcher's stream element for element — contexts in the
    same (increasing position) order, subsampling coins included."""
    sents = corpus(seed=4)
    vocab = build_vocab(sents)
    B, W = 24, 2
    pair = CBOWBatcher(sents, vocab, W, sample=sample, seed=9)
    sten = CBOWBatcher(sents, vocab, W, sample=sample, seed=9)
    want = _pair_stream(pair.epoch(B))
    got = _pair_stream(stencil_to_cbow(b, W) for b in sten.epoch_stencil(B))
    assert len(want) > 0
    assert got == want


def test_stencil_working_set_bounded():
    """The acceptance bound this rendering exists for: every batch's
    gather working set is at most B + 2W rows (in a span of whole lane
    tiles) — vs B * 2W context gathers in the per-pair layout."""
    sents = corpus(n_sent=60, seed=7)
    vocab = build_vocab(sents)
    B, W = 32, 3
    batcher = CBOWBatcher(sents, vocab, W, seed=3)
    n_batches = 0
    for b in batcher.epoch_stencil(B):
        n_batches += 1
        assert b.span == span_positions(B, W) == 128  # fixed span capacity
        assert int(np.sum(b.sent_id >= 0)) <= B + 2 * W
        # and strictly below the per-pair working set at this shape
        assert b.span < B * 2 * W
    assert n_batches > 1


def test_stencil_batch_padding_conventions():
    """Wire-format padding: tokens 0 / sent_id -1 beyond the span fill,
    center_pos -1 / half 0 beyond n_words — the device step's masks key
    off exactly these sentinels."""
    sents = corpus(n_sent=5, seed=1)
    vocab = build_vocab(sents)
    B, W = 256, 2                        # one underfull batch
    batches = list(CBOWBatcher(sents, vocab, W, seed=3).epoch_stencil(B))
    tail = batches[-1]
    assert 0 < tail.n_words < B
    assert tail.tokens.dtype == np.int32
    assert tail.sent_id.dtype == np.int32
    assert (tail.center_pos[tail.n_words:] == -1).all()
    assert (tail.half[tail.n_words:] == 0).all()
    pad = tail.sent_id < 0
    assert (tail.tokens[pad] == 0).all()
    # every real center points at a valid span row of its own sentence
    for i in range(tail.n_words):
        p = int(tail.center_pos[i])
        assert 0 <= p < tail.span and tail.sent_id[p] >= 0
        assert tail.half[i] >= 1


# -- native batcher parity -------------------------------------------------


needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native loader not built")


@pytest.fixture
def corpus_file(tmp_path):
    sents = synthetic_corpus(30, vocab_size=80, length=20, seed=12)
    p = tmp_path / "corpus.txt"
    with open(p, "w") as f:
        for s in sents:
            f.write(" ".join(map(str, s)) + "\n")
    return str(p)


@needs_native
def test_native_stencil_expands_to_native_pair_stream(corpus_file):
    """The C++ stencil assembler consumes its rng in exactly the pair
    batcher's draw order, so at the same seed the expanded stream is
    identical in order — the native mirror of the Python parity test."""
    vocab_c, tokens, offsets = native.load_corpus_native(corpus_file)
    B, W = 48, 2
    pair = native.NativeCBOWBatcher(tokens, offsets, vocab_c, window=W,
                                    seed=21)
    sten = native.NativeCBOWBatcher(tokens, offsets, vocab_c, window=W,
                                    seed=21)
    want = _pair_stream(pair.epoch(B))
    got = _pair_stream(stencil_to_cbow(b, W) for b in sten.epoch_stencil(B))
    assert len(want) > 0
    assert got == want


@needs_native
def test_native_stencil_wire_format_matches_python(corpus_file):
    """Cross-backend wire format: same dtypes, same span capacity, same
    padding sentinels, same working-set bound — and (rng streams aside:
    numpy PCG64 vs C++ mt19937_64, so per-position window shrinks
    differ) the same epoch COVERAGE: without subsampling every corpus
    position is a center exactly once in both backends' expansions."""
    vocab_c, tokens, offsets = native.load_corpus_native(corpus_file)
    vocab_py = build_vocab(load_corpus(corpus_file))
    B, W = 48, 2
    nat = list(native.NativeCBOWBatcher(
        tokens, offsets, vocab_c, window=W, seed=5).epoch_stencil(B))
    pys = list(CBOWBatcher(load_corpus(corpus_file), vocab_py, W,
                           seed=5).epoch_stencil(B))
    S = span_positions(B, W)
    assert S == 128 >= B + 2 * W
    for b in nat + pys:
        assert b.tokens.dtype == np.int32 and b.tokens.shape == (S,)
        assert b.sent_id.dtype == np.int32
        assert b.center_pos.dtype == np.int32
        assert b.half.dtype == np.int32
        assert b.span == S
        assert b.pack().dtype == np.int32
        for got, want in zip(unpack_span(b.pack(), B),
                             (b.tokens, b.sent_id, b.center_pos, b.half)):
            np.testing.assert_array_equal(got, want)
        assert (b.center_pos[b.n_words:] == -1).all()
        assert (b.tokens[b.sent_id < 0] == 0).all()
    def coverage(batches):
        centers = np.concatenate(
            [stencil_to_cbow(b, W).centers[:b.n_words] for b in batches])
        return np.bincount(centers, minlength=len(vocab_c))

    got, want = coverage(nat), coverage(pys)
    np.testing.assert_array_equal(got, np.asarray(vocab_c.counts))
    np.testing.assert_array_equal(want, np.asarray(vocab_py.counts))


# -- span push (transfer/xla.py push_span) ---------------------------------


def test_push_span_matches_generic_push_unit_counts():
    """counts == 1 per row: push_span's sort-free dedup must equal the
    generic sorted push exactly (duplicate slots summed then applied
    once, -1 rows dropped, mean over contribution counts)."""
    m = make_model(stencil=0)
    m.build(corpus(seed=2))
    state = m.table.state
    rng = np.random.default_rng(0)
    S, d = 37, m.len_vec
    cap = next(iter(state.values())).shape[0]
    slots = rng.integers(0, min(cap, 20), size=S).astype(np.int32)
    slots[::7] = -1                       # padding rows must drop
    grads = {"v": rng.normal(size=(S, d)).astype(np.float32)}
    counts = np.ones(S, np.float32)
    a = m.transfer.push_span(state, slots, grads, counts, m.access,
                             mean=True)
    b = m.transfer.push(state, jnp.asarray(slots), grads, m.access,
                        mean=True)
    for f in b:
        np.testing.assert_allclose(np.asarray(a[f]), np.asarray(b[f]),
                                   atol=1e-5, rtol=1e-5)


def test_push_span_matches_expanded_contribution_push():
    """Data counts: a span row carrying the SUM of c_i contributions
    with counts[i] = c_i must land exactly like pushing those c_i
    contributions through the generic path row by row."""
    m = make_model(stencil=0)
    m.build(corpus(seed=2))
    state = m.table.state
    rng = np.random.default_rng(3)
    S, d = 23, m.len_vec
    slots = rng.integers(0, 12, size=S).astype(np.int32)
    slots[5] = slots[6] = -1
    counts = rng.integers(0, 4, size=S).astype(np.float32)
    g = rng.normal(size=(S, d)).astype(np.float32)
    g[counts == 0] = 0.0                  # untouched rows carry no grad
    a = m.transfer.push_span(state, slots, {"v": g}, counts, m.access,
                             mean=True)
    exp_slots, exp_grads = [], []
    for i in range(S):
        c = int(counts[i])
        for _ in range(c):
            exp_slots.append(slots[i])
            exp_grads.append(g[i] / c)
    b = m.transfer.push(
        state, jnp.asarray(np.asarray(exp_slots, np.int32)),
        {"v": jnp.asarray(np.stack(exp_grads))}, m.access, mean=True)
    for f in b:
        np.testing.assert_allclose(np.asarray(a[f]), np.asarray(b[f]),
                                   atol=1e-5, rtol=1e-5)


# -- device rendering golden checks ----------------------------------------


def _first_stencil_batch(sents, model, B):
    batcher = CBOWBatcher(sents, model.vocab, model.window,
                          model.sample, seed=13)
    return next(iter(batcher.epoch_stencil(B)))


def _dense_from_pushes(model, pushes):
    """Scatter a stencil gradient phase's pushes into dense vocab-key
    space, applying each push family's own normalization (mean over
    row-contribution counts; data counts for the span family)."""
    slot_to_key = {int(i): int(k) for k, i in zip(
        model.vocab.keys.tolist(),
        np.asarray(model._slot_of_vocab).tolist())}
    V = int(model.vocab.keys.max()) + 1
    d = model.len_vec
    dense = {f: np.zeros((V, d), np.float64) for f in ("h", "v")}
    for spec in pushes:
        slots_np = np.asarray(spec.slots).reshape(-1).tolist()
        counts = (np.asarray(spec.counts, np.float64)
                  if getattr(spec, "counts", None) is not None else None)
        for f, g in spec.grads.items():
            g = np.asarray(g, np.float64)
            sums, cnt = {}, {}
            for j, s in enumerate(slots_np):
                if s < 0:
                    continue
                sums[s] = sums.get(s, 0.0) + g[j]
                cnt[s] = cnt.get(s, 0.0) + (counts[j] if counts is not None
                                            else 1.0)
            for s, tot in sums.items():
                dense[f][slot_to_key[s]] += (
                    tot / max(cnt[s], 1.0) if spec.mean else tot)
    return dense["h"], dense["v"]


def test_stencil_grads_match_numpy_oracle(devices8):
    """Golden check: the stencil gradient phase vs the sequential numpy
    oracle run on the EXPANDED per-pair view of the same batch, with the
    exact negatives the step drew (same sampling stream as the gather
    rendering — the parity-negatives variant's anchor)."""
    model = make_model()
    sents = corpus(seed=3)
    model.build(sents)
    state = model.table.state
    B, K = 24, model.negative
    batch = _first_stencil_batch(sents, model, B)
    assert batch.n_words == B             # full batch, no padding
    key = jax.random.key(7)

    grads_fn = model._build_grads()
    assert model.resolved_rendering == "stencil"
    span, shape = span_args(batch)
    pushes, es, ec = grads_fn(
        state, model._slot_of_vocab, model._alias_prob, model._alias_idx,
        *span, key, **shape)
    got_h, got_v = _dense_from_pushes(model, pushes)

    # identical randomness: the negatives the step drew, in key space
    negs_v = np.asarray(sample_alias(key, model._alias_prob,
                                     model._alias_idx, (B, K)))
    negs = model.vocab.keys[negs_v].astype(np.int64)
    exp = stencil_to_cbow(batch, model.window)
    V = int(model.vocab.keys.max()) + 1
    h = np.zeros((V, model.len_vec), np.float32)
    v = np.zeros((V, model.len_vec), np.float32)
    sov = np.asarray(model._slot_of_vocab)
    for kk, i in zip(model.vocab.keys.tolist(), sov.tolist()):
        h[int(kk)] = np.asarray(state["h"])[i]
        v[int(kk)] = np.asarray(state["v"])[i]
    ctx_keys = np.zeros_like(exp.contexts, np.int64)
    ctx_keys[exp.ctx_mask] = np.asarray(
        model.vocab.keys)[exp.contexts[exp.ctx_mask]].astype(np.int64)
    center_keys = model.vocab.keys[exp.centers].astype(np.int64)

    want_h, want_v, w_es, w_ec = cbow_batch_grads(
        h, v, center_keys, ctx_keys, exp.ctx_mask, negs, model.alpha,
        quantized_sigmoid=False)
    assert int(ec) == w_ec
    np.testing.assert_allclose(float(es), w_es, rtol=1e-4)
    np.testing.assert_allclose(got_h, want_h, atol=2e-6, rtol=1e-3)
    np.testing.assert_allclose(got_v, want_v, atol=2e-6, rtol=1e-3)


@pytest.mark.slow
def test_stencil_step_matches_gather_step(devices8):
    """One full donated step (pull + grads + span push) on the stencil
    wire format vs the already-oracle-pinned gather rendering on the
    expanded batch, same key: post-step states must agree to fp32
    reassociation tolerance — including a padded tail batch, whose
    masked rows must contribute nothing on either side.

    Slow lane (~6.5s: two step compiles x two batch shapes): tier-1
    keeps test_stencil_train_matches_gather_train, which proves the
    same stencil==gather equivalence end-to-end through train()."""
    sents = corpus(seed=3)
    m_st = make_model()
    m_ga = make_model(stencil=0)
    m_st.build(sents)
    build_per_pair(m_ga, sents)
    step_st = m_st._build_step()
    step_ga = m_ga._build_step()
    for B in (24, 512):                   # full batch / padded tail
        batch = _first_stencil_batch(sents, m_st, B)
        if B == 512:
            assert batch.n_words < B
        exp = stencil_to_cbow(batch, m_st.window)
        # the jitted steps DONATE their state and key arguments: hand
        # each call fresh copies so the models' live buffers survive
        # both rounds
        span, shape = span_args(batch)
        st1, _k1, t1, es1 = step_st(
            {f: jnp.array(v) for f, v in m_st.table.state.items()},
            m_st._slot_of_vocab, m_st._alias_prob,
            m_st._alias_idx, *span, jax.random.key(11), **shape)
        st2, _k2, t2, es2 = step_ga(
            {f: jnp.array(v) for f, v in m_ga.table.state.items()},
            m_ga._slot_of_vocab, m_ga._alias_prob,
            m_ga._alias_idx, jnp.asarray(exp.centers),
            jnp.asarray(exp.contexts), jnp.asarray(exp.ctx_mask),
            jax.random.key(11))
        assert _Tally.read(t1)["pair_count"] == _Tally.read(t2)["pair_count"]
        np.testing.assert_allclose(float(es1), float(es2), rtol=1e-5)
        for f in st2:
            np.testing.assert_allclose(np.asarray(st1[f]),
                                       np.asarray(st2[f]),
                                       atol=1e-5, rtol=1e-5)


def test_stencil_train_matches_gather_train(devices8):
    """End-to-end: 3 epochs through the public train() path — identical
    batch streams (same seed), identical per-step keys, so the loss
    trajectories must coincide."""
    sents = corpus(seed=3)
    m_st = make_model()
    m_ga = make_model(stencil=0)
    losses_st = m_st.train(sents, niters=3, batch_size=64)
    losses_ga = m_ga.train(sents, niters=3, batch_size=64)
    assert losses_st[-1] < losses_st[0]
    np.testing.assert_allclose(losses_st, losses_ga, rtol=1e-4)


def test_stencil_shared_pool_variant_trains(devices8):
    """stencil + shared_negatives (the 1M-vocab bench composition):
    resolves to the stencil_shared rendering and the loss decreases."""
    m = make_model(word2vec={"shared_negatives": 1, "shared_pool": 64})
    losses = m.train(corpus(seed=3), niters=3, batch_size=64)
    assert m.resolved_rendering == "stencil_shared"
    assert losses[-1] < losses[0], losses


# -- composition guards ----------------------------------------------------


def test_stencil_rejects_skipgram():
    m = make_model(word2vec={"sg": 1})
    m.build(corpus())
    with pytest.raises(ValueError, match="CBOW-only"):
        m._build_grads()


def test_stencil_requires_xla_transfer():
    m = make_model(cluster={"transfer": "local"})
    m.build(corpus())
    with pytest.raises(ValueError, match="push_span"):
        m._build_grads()


def test_stencil_rejects_hogwild(devices8):
    m = make_model(word2vec={"async_mode": "hogwild"})
    with pytest.raises(ValueError, match="hogwild"):
        m.train(corpus(), niters=1, batch_size=64)


# -- hogwild multi-process fallback (satellite of the same PR) -------------


def test_hogwild_multiprocess_falls_back_to_snapshot(devices8, monkeypatch):
    """Multi-process + async_mode=hogwild no longer raises
    NotImplementedError: train() routes to the measured snapshot
    bounded-staleness mode (local_steps >= 2) with a logged notice.
    process_count is faked; the distributed wrappers are stubbed so the
    single-process test actually executes the fallback path."""
    import swiftmpi_tpu.data.distributed as dist
    import swiftmpi_tpu.models.word2vec as w2v_mod

    class PassThrough:
        def __init__(self, batcher, mesh):
            self._b = batcher

        def epoch(self, batch_size):
            return self._b.epoch(batch_size)

    warned = []
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(dist, "shard_sentences", lambda s, *a, **k: s)
    monkeypatch.setattr(dist, "DistributedBatcher", PassThrough)
    monkeypatch.setattr(w2v_mod.log, "warning",
                        lambda msg, *a: warned.append(msg % a))
    m = make_model(stencil=0, word2vec={"async_mode": "hogwild"})
    losses = m.train(corpus(seed=3), niters=2, batch_size=64)
    assert m.local_steps >= 2
    assert any("snapshot bounded" in w for w in warned)
    # snapshot mode: the step is the (grads, apply) pair, not hogwild's
    assert isinstance(m._step, tuple) and len(m._step) == 2
    assert len(losses) == 2 and np.isfinite(losses).all()


def test_stencil_rejects_multiprocess(monkeypatch):
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    m = make_model()
    with pytest.raises(ValueError, match="single-process"):
        m.train(corpus(), niters=1, batch_size=64)


# -- the default rendering (ISSUE 36): spans under subsampling --------------


def zipf_stream(n_tokens=6000, vocab=200, sentence=40, seed=0):
    """(vocab, tokens, offsets) of a Zipf-1.0 stream of 40-token
    sentences with every key once: the benchmark traffic's shape, toy
    size.  Frequent words fail the center gate at ``sample`` 1e-2."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, vocab + 1)
    ranks = np.concatenate([np.arange(vocab),
                            rng.choice(vocab, n_tokens - vocab,
                                       p=p / p.sum())])
    rng.shuffle(ranks)
    counts = np.bincount(ranks, minlength=vocab).astype(np.int64)
    keys = np.arange(1, vocab + 1, dtype=np.uint64)
    order = np.lexsort((keys, -counts))      # count desc, key asc
    index_of = np.empty(vocab, np.int32)
    index_of[order] = np.arange(vocab, dtype=np.int32)
    voc = Vocab(keys[order], counts[order],
                dict(zip(keys[order].tolist(), range(vocab))))
    offsets = np.append(np.arange(0, n_tokens, sentence, dtype=np.int64),
                        np.int64(n_tokens))
    return voc, index_of[ranks].astype(np.int32), offsets


class PerPairOnly:
    """A batcher that renders no spans: what keeps a CBOW model on the
    per-pair rendering."""

    def __init__(self, inner):
        self.inner, self.vocab = inner, inner.vocab

    def epoch(self, batch_size):
        return self.inner.epoch(batch_size)


SAMPLE = 1e-2


def default_model(**overrides):
    """A CBOW model as a cell's conf leaves it: no ``stencil`` key."""
    return make_model(stencil=0, word2vec={"sample": SAMPLE, "window": 3},
                      **overrides)


@needs_native
def test_native_spans_hold_full_batches_under_subsampling():
    """The native span batcher sizes its span from its own keep
    probabilities, so under subsampling every batch but the epoch's last
    closes at B centers, none on a full span — and the expanded stream
    is ``epoch()``'s at the same seed, element for element."""
    voc, tokens, offsets = zipf_stream()
    B, W = 256, 3
    sten = native.NativeCBOWBatcher(tokens, offsets, voc, W, SAMPLE, seed=7)
    pair = native.NativeCBOWBatcher(tokens, offsets, voc, W, SAMPLE, seed=7)
    assert 0.5 < sten.keep_mean < 0.9        # the gate bites
    S = span_positions(B, W, sten.keep_mean)
    assert S % 128 == 0 and S > B / sten.keep_mean + 2 * W
    assert span_positions(B, W) == 384       # none gated: B + 2W, in tiles
    batches = list(sten.epoch_stencil(B))
    assert len(batches) > 8
    assert [b.n_words for b in batches[:-1]] == [B] * (len(batches) - 1)
    for b in batches:
        assert b.span == S and b.packed.shape == (2 * S + 2 * B,)
        assert np.shares_memory(b.tokens, b.packed)
        assert int((b.sent_id >= 0).sum()) < S           # never full
    want = _pair_stream(pair.epoch(B))
    got = _pair_stream(stencil_to_cbow(b, W) for b in batches)
    assert len(want) > 8 * B and got == want


@needs_native
@pytest.mark.parametrize("which", ["full", "padded_tail"])
def test_span_step_equals_per_pair_step_row_for_row(which, devices8):
    """The dense-shift rendering against the per-pair step on the
    expansion of the same batch, same key: every touched row of ``h``,
    ``v``, ``h2sum``, ``v2sum`` and the loss, on a Zipf stream under
    ``sample`` > 0 — sentence boundaries every 40 tokens, shrunk
    windows, gated centers, duplicate tokens all over the span, and (the
    epoch's tail) padded centers."""
    voc, tokens, offsets = zipf_stream()
    m_sp, m_pp = default_model(), default_model()
    m_sp.build_from_vocab(voc)
    m_pp.build_from_vocab(voc)
    assert m_sp.stencil == 1 and m_sp._build_grads() \
        and m_sp.resolved_rendering == "stencil"
    m_pp.stencil = 0
    B, W = 256, m_sp.window
    batches = list(native.NativeCBOWBatcher(
        tokens, offsets, voc, W, SAMPLE, seed=3).epoch_stencil(B))
    batch = batches[0] if which == "full" else batches[-1]
    assert (batch.n_words == B) == (which == "full")
    real = batch.tokens[batch.sent_id >= 0]
    assert len(np.unique(real)) < 0.5 * len(real)        # duplicates
    assert len(np.unique(batch.sent_id)) > 5             # boundaries
    assert len(np.unique(batch.half[:batch.n_words])) == W    # shrunk
    # gated centers: positions inside the span that are no center
    assert batch.n_words < 0.9 * int((batch.sent_id >= 0).sum())
    exp = stencil_to_cbow(batch, W)

    def fresh(m):
        return {f: jnp.array(v) for f, v in m.table.state.items()}

    before = {f: np.asarray(v) for f, v in m_sp.table.state.items()}
    span, shape = span_args(batch)
    got, _k1, t1, es1 = m_sp._build_step()(
        fresh(m_sp), m_sp._slot_of_vocab, m_sp._alias_prob,
        m_sp._alias_idx, *span, jax.random.key(5), **shape)
    want, _k2, t2, es2 = m_pp._build_step()(
        fresh(m_pp), m_pp._slot_of_vocab, m_pp._alias_prob,
        m_pp._alias_idx, jnp.asarray(exp.centers),
        jnp.asarray(exp.contexts), jnp.asarray(exp.ctx_mask),
        jax.random.key(5))
    assert _Tally.read(t1)["pair_count"] == _Tally.read(t2)["pair_count"] > 0
    np.testing.assert_allclose(float(es1), float(es2), rtol=1e-5)
    for f in ("h", "v", "h2sum", "v2sum"):
        a, b = np.asarray(got[f]), np.asarray(want[f])
        moved = np.any(b != before[f], axis=1)
        assert moved.sum() > 20, f
        # the same rows move, and to the same values
        np.testing.assert_array_equal(np.any(a != before[f], axis=1), moved)
        np.testing.assert_allclose(a[moved], b[moved], atol=1e-6, rtol=1e-5,
                                   err_msg=f)


def test_uncovered_span_position_writes_no_row(devices8):
    """A span position no center's window covers is pushed as slot -1:
    its word's ``v`` row (and accumulator) stays bit for bit, even where
    the word is in the vocabulary and the span names it."""
    from swiftmpi_tpu.data.text import StencilBatch

    m = make_model(stencil=0, word2vec={"window": 3})
    m.build(corpus(seed=3))
    assert m.stencil == 1
    V, S, B = len(m.vocab), 128, 8
    tokens = np.zeros(S, np.int32)
    sent = np.full(S, -1, np.int32)
    # one sentence of 12 positions: words 1..12; centers at 2 and 3 with
    # half 1 reach positions 1..4 only; a second sentence of 3 with its
    # middle the center, half 3
    tokens[:12], sent[:12] = np.arange(1, 13), 0
    tokens[12:15], sent[12:15] = [13, 14, 15], 1
    cpos = np.full(B, -1, np.int32)
    half = np.zeros(B, np.int32)
    cpos[:3], half[:3] = [2, 3, 13], [1, 1, 3]
    batch = StencilBatch(tokens, sent, cpos, half, 3)
    assert V > 16
    span, shape = span_args(batch)
    grads_fn = m._build_grads()
    pushes, _es, _ec = grads_fn(
        m.table.state, m._slot_of_vocab, m._alias_prob, m._alias_idx,
        *span, jax.random.key(1), **shape)
    v_push = next(p for p in pushes if "v" in p.grads)
    covered = np.asarray(v_push.slots) >= 0
    assert covered[[1, 2, 3, 4, 12, 14]].all()     # in a window
    assert not covered[[0, 5, 11, 13]].any()       # in none (13: a center
    assert covered.sum() == 6                      # alone is no context)
    np.testing.assert_array_equal(
        np.asarray(v_push.counts)[:6], [0, 1, 1, 1, 1, 0])
    before = {f: np.asarray(v) for f, v in m.table.state.items()}
    after, *_sums = m._build_step()(
        {f: jnp.array(v) for f, v in m.table.state.items()},
        m._slot_of_vocab, m._alias_prob, m._alias_idx, *span,
        jax.random.key(1), **shape)
    sov = np.asarray(m._slot_of_vocab)
    for f in ("v", "v2sum"):
        a = np.asarray(after[f])
        moved = np.any(a != before[f], axis=1)
        assert moved[sov[tokens[[1, 2, 3, 4, 12, 14]]]].all(), f
        assert not moved[sov[tokens[[0, 5, 11, 13]]]].any(), f
        assert moved.sum() == 6, f


@needs_native
@pytest.mark.parametrize("worker, w2v", [
    ({}, {}), ({"inner_steps": 3}, {}), ({"pipeline": 2}, {}),
    ({}, {"local_steps": 2})],
    ids=["single", "fused", "pipelined", "snapshot"])
def test_span_train_equals_per_pair_train(worker, w2v, devices8):
    """Through ``train()``, every loop: a CBOW model handed the native
    batcher resolves to spans on its own and walks the per-pair run's
    trajectory (same stream, same keys) — and handed a batcher that
    renders none it keeps the per-pair rendering, visibly."""
    voc, tokens, offsets = zipf_stream()

    def run(wrap):
        m = default_model(worker=worker)
        for k, v in w2v.items():
            m.config.set("word2vec", k, v)
            setattr(m, k, v)
        m.build_from_vocab(voc)
        assert m.stencil == 1                # at build time, before train
        inner = native.NativeCBOWBatcher(tokens, offsets, voc, m.window,
                                         SAMPLE, seed=11)
        losses = m.train(batcher=wrap(inner), niters=2, batch_size=256)
        return m, losses

    m_sp, got = run(lambda b: b)
    m_pp, want = run(PerPairOnly)
    assert (m_sp.stencil, m_sp.resolved_rendering) == (1, "stencil")
    assert (m_pp.stencil, m_pp.resolved_rendering) == (0, "gather")
    assert got[-1] < got[0]
    np.testing.assert_allclose(got, want, rtol=1e-4)
    # a model that fell back takes spans again when it is handed them
    inner = native.NativeCBOWBatcher(tokens, offsets, voc, m_pp.window,
                                     SAMPLE, seed=12)
    m_pp.train(batcher=inner, niters=1, batch_size=256)
    assert (m_pp.stencil, m_pp.resolved_rendering) == (1, "stencil")


@pytest.mark.parametrize("overrides, why", [
    ({"word2vec": {"sg": 1}}, "skip-gram is per-pair by nature"),
    ({"word2vec": {"async_mode": "hogwild"}}, "hogwild groups per-pair"),
    ({"cluster": {"transfer": "local"}}, "no counted push"),
])
def test_who_keeps_the_per_pair_rendering(overrides, why, devices8):
    m = make_model(stencil=0, **overrides)
    m.build(corpus(seed=3))
    assert m.stencil == 0, why


def test_python_batcher_of_train_keeps_per_pair(devices8):
    """``train(sentences)`` makes the Python batcher, whose stream the
    per-pair tests pin: the model settles on per-pair batches there."""
    m = make_model(stencil=0)
    sents = corpus(seed=3)
    m.build(sents)
    assert m.stencil == 1
    m.train(sents, niters=1, batch_size=64)
    assert (m.stencil, m.resolved_rendering) == (0, "gather")
