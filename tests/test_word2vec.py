"""word2vec tests: sampling ops, batcher, fused step training, checkpoints."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from swiftmpi_tpu.data.text import (CBOWBatcher, build_vocab, load_corpus,
                                    synthetic_corpus, tokenize)
from swiftmpi_tpu.models.word2vec import Word2Vec, _Tally
from swiftmpi_tpu.ops import (MAX_EXP, build_unigram_alias, sample_alias,
                              sigmoid_clipped, subsample_keep_prob)
from swiftmpi_tpu.utils import ConfigParser


# -- ops ------------------------------------------------------------------

def test_alias_sampler_matches_unigram_075():
    counts = np.array([100, 10, 1, 50], np.float64)
    prob, alias = build_unigram_alias(counts)
    draws = sample_alias(jax.random.key(0), jnp.asarray(prob),
                         jnp.asarray(alias), (200_000,))
    freq = np.bincount(np.asarray(draws), minlength=4) / 200_000
    expect = counts ** 0.75
    expect /= expect.sum()
    np.testing.assert_allclose(freq, expect, atol=0.01)


def _alias_on_numpy_scalars(counts, power=0.75):
    """`build_unigram_alias` as it stood until PR 47: Vose's pairing on
    numpy float64 scalars."""
    w = np.asarray(counts, np.float64) ** power
    p = w / w.sum() * len(w)
    prob = np.ones(len(w), np.float64)
    alias = np.arange(len(w), dtype=np.int32)
    small = [i for i, x in enumerate(p) if x < 1.0]
    large = [i for i, x in enumerate(p) if x >= 1.0]
    while small and large:
        s, l = small.pop(), large.pop()
        prob[s] = p[s]
        alias[s] = l
        p[l] = p[l] - (1.0 - p[s])
        (small if p[l] < 1.0 else large).append(l)
    for i in small + large:
        prob[i] = 1.0
    return prob.astype(np.float32), alias


@pytest.mark.parametrize("counts", [
    np.array([7.0]), np.full(1000, 3.0), np.arange(1, 5001),
    1e6 / np.arange(1, 20_001) ** 1.07,       # a Zipf stream's counts
    np.random.default_rng(3).integers(1, 10_000, 30_001),
], ids=["one", "ties", "ramp", "zipf", "random"])
def test_alias_tables_are_the_numpy_pairing_s_bit_for_bit(counts):
    """PR 47 pairs the buckets on Python floats and lists (half the time
    at 1.8 M words, a set-up cost alone): the same IEEE doubles, so the
    same ``prob`` and ``alias``, bit for bit, dtype for dtype."""
    prob, alias = build_unigram_alias(counts)
    want_prob, want_alias = _alias_on_numpy_scalars(counts)
    assert (prob.dtype, alias.dtype) == (np.float32, np.int32)
    np.testing.assert_array_equal(prob, want_prob)
    np.testing.assert_array_equal(alias, want_alias)


@pytest.mark.parametrize("shape,mode", [
    ((5,), "per_draw"),
    ((776,), "per_draw"),
    ((777,), "per_vocab"),          # as many draws as words: the pack
    ((1000,), "per_vocab"),
    ((16, 20), "per_draw"),
    ((64, 20), "per_vocab"),
    ((8, 4, 5), "per_draw"),
    ((4, 40, 5), "per_vocab"),
])
def test_sample_alias_slots_is_fused_sample_plus_lookup(shape, mode):
    """The fused sampler must stay draw-stream BIT-IDENTICAL to
    sample_alias + slot_of_vocab[negs] — training uses the fused form
    while the oracle-parity tests reproduce negatives via sample_alias,
    so any drift would silently unpin the golden checks.  Both forms of
    the rejected draw's slot lookup (per draw below V draws, per
    vocabulary word from V up), a slot map longer than the vocabulary
    and unplaced words (slot -1)."""
    from swiftmpi_tpu.ops.sampling import (alias_slot_lookups,
                                           sample_alias_slots)
    V = 777
    rng = np.random.default_rng(5)
    prob, alias = build_unigram_alias(rng.integers(1, 500, V))
    prob_d, alias_d = jnp.asarray(prob), jnp.asarray(alias)
    sov = rng.permutation(2048)[:900].astype(np.int32)
    sov[rng.choice(V, 60, replace=False)] = -1
    assert alias_slot_lookups(V, shape) == (
        mode, V if mode == "per_vocab" else int(np.prod(shape)))
    key = jax.random.key(11)
    negs, neg_slots = sample_alias_slots(
        key, prob_d, alias_d, jnp.asarray(sov), shape)
    want = sample_alias(key, prob_d, alias_d, shape)
    assert negs.shape == neg_slots.shape == shape
    assert negs.dtype == neg_slots.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(negs), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(neg_slots),
                                  sov[np.asarray(negs)])
    if negs.size >= 160:
        assert (np.asarray(neg_slots) == -1).any()


def test_subsample_keep_prob_rule():
    counts = np.array([1000, 10], np.float64)
    keep = subsample_keep_prob(counts, sample=0.01)
    # freq = [1000/1010, 10/1010]; keep = min(1, sqrt(sample/freq))
    np.testing.assert_allclose(
        keep, np.minimum(1, np.sqrt(0.01 / (counts / counts.sum()))),
        rtol=1e-6)
    np.testing.assert_array_equal(subsample_keep_prob(counts, -1), 1)


def test_sigmoid_clipped_saturation():
    f = jnp.array([-10.0, -MAX_EXP - 1e-3, 0.0, MAX_EXP + 1e-3, 10.0])
    s = np.asarray(sigmoid_clipped(f))
    assert s[0] == 0.0 and s[1] == 0.0
    assert s[2] == pytest.approx(0.5)
    assert s[3] == 1.0 and s[4] == 1.0


# -- data -----------------------------------------------------------------

def test_tokenize_modes():
    assert tokenize("1 2 30", "int") == [1, 2, 30]
    h = tokenize("hello world", "bkdr")
    assert len(h) == 2 and all(isinstance(x, int) for x in h)
    assert tokenize("hello", "int") == tokenize("hello", "bkdr")  # fallback


def test_build_vocab_orders_by_frequency():
    v = build_vocab([[1, 1, 2], [1, 3, 3]])
    assert v.keys[0] == 1 and v.counts[0] == 3
    assert v.total_words == 6
    assert v.index[1] == 0


def test_load_corpus_chunks_single_line(tmp_path):
    p = tmp_path / "text8ish.txt"
    p.write_text(" ".join(str(i % 7) for i in range(100)))
    sents = load_corpus(str(p), max_sentence_length=30)
    assert [len(s) for s in sents] == [30, 30, 30, 10]


def test_cbow_batcher_shapes_and_window():
    corpus = synthetic_corpus(20, vocab_size=50, length=15, seed=1)
    vocab = build_vocab(corpus)
    b = CBOWBatcher(corpus, vocab, window=3, seed=7)
    batches = list(b.epoch(32))
    assert all(bt.centers.shape == (32,) for bt in batches)
    assert all(bt.contexts.shape == (32, 6) for bt in batches)
    for bt in batches:
        # masked rows only in the padded tail
        assert bt.ctx_mask[:bt.n_words].any(axis=1).all()
        # context never contains more than 2W valid entries (trivially) and
        # padding is zero
        assert (bt.contexts[~bt.ctx_mask] == 0).all()


def test_cbow_batcher_epoch_is_deterministic_given_seed():
    corpus = synthetic_corpus(5, vocab_size=20, length=10)
    vocab = build_vocab(corpus)
    a = list(CBOWBatcher(corpus, vocab, 2, seed=3).epoch(16))
    b = list(CBOWBatcher(corpus, vocab, 2, seed=3).epoch(16))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.centers, y.centers)
        np.testing.assert_array_equal(x.contexts, y.contexts)


# -- model ----------------------------------------------------------------

def make_model(**overrides):
    cfg = ConfigParser().update({
        "cluster": {"transfer": "xla"},
        "word2vec": {"len_vec": 16, "window": 2, "negative": 5,
                     "sample": -1, "learning_rate": 0.05,
                     "min_sentence_length": 2},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 512},
    })
    for sec, kv in overrides.items():
        for k, v in kv.items():
            cfg.set(sec, k, v)
    return Word2Vec(config=cfg)


def test_w2v_trains_and_loss_decreases(devices8):
    corpus = synthetic_corpus(60, vocab_size=100, length=18, seed=2)
    model = make_model()
    losses = model.train(corpus, niters=5, batch_size=128)
    assert len(losses) == 5
    assert losses[-1] < losses[0], losses


def test_w2v_checkpoint_roundtrip(tmp_path, devices8):
    corpus = synthetic_corpus(20, vocab_size=40, length=12, seed=4)
    model = make_model()
    model.train(corpus, niters=1, batch_size=64)
    path = str(tmp_path / "emb.txt")
    n = model.save(path)
    assert n == len(model.table.key_index)
    # reference layout: key \t v-vector \t h-vector
    parts = open(path).readline().rstrip("\n").split("\t")
    assert len(parts) == 3
    assert len(parts[1].split()) == 16 and len(parts[2].split()) == 16

    model2 = make_model()
    model2._capacity_per_shard = model.table.key_index.capacity_per_shard
    model2.load(path)
    k = int(model.vocab.keys[0])
    np.testing.assert_allclose(model.embedding(k), model2.embedding(k),
                               rtol=1e-6)


def test_w2v_embeddings_capture_cooccurrence(devices8):
    # Words that co-occur should end up closer than random pairs.
    rng = np.random.default_rng(0)
    # build corpus of sentences drawn from 2 disjoint topic vocabularies
    topic_a = list(range(1, 21))
    topic_b = list(range(21, 41))
    corpus = []
    for i in range(120):
        words = rng.choice(topic_a if i % 2 == 0 else topic_b, size=12)
        corpus.append([int(w) for w in words])
    model = make_model()
    model.train(corpus, niters=8, batch_size=128)

    def vec(k):
        v = model.embedding(k)
        return v / (np.linalg.norm(v) + 1e-9)

    within = np.mean([vec(topic_a[i]) @ vec(topic_a[j])
                      for i in range(5) for j in range(5) if i != j])
    across = np.mean([vec(topic_a[i]) @ vec(topic_b[j])
                      for i in range(5) for j in range(5)])
    assert within > across, (within, across)


def test_w2v_skipgram_trains_and_loss_decreases(devices8):
    corpus = synthetic_corpus(60, vocab_size=100, length=18, seed=3)
    model = make_model(word2vec={"sg": 1})
    losses = model.train(corpus, niters=5, batch_size=64)
    assert len(losses) == 5
    assert losses[-1] < losses[0], losses


def test_w2v_skipgram_grads_match_numpy():
    """SG gradient phase vs a direct numpy transcription of the word2vec.c
    skip-gram inner loop (mean-normalized per key, as at push time)."""
    model = make_model(word2vec={"sg": 1, "negative": 3, "len_vec": 8,
                                 "window": 2})
    corpus = synthetic_corpus(10, vocab_size=30, length=10, seed=5)
    model.build(corpus)
    batcher = CBOWBatcher(corpus, model.vocab, model.window)
    batch = next(batcher.epoch(16))
    grads_fn = jax.jit(model._build_grads())
    key = jax.random.key(7)
    pushes, es, ec = grads_fn(
        model.table.state, model._slot_of_vocab, model._alias_prob,
        model._alias_idx, jnp.asarray(batch.centers),
        jnp.asarray(batch.contexts), jnp.asarray(batch.ctx_mask), key)
    es, ec = float(es), int(ec)
    # one push per gradient family, whichever comes first
    by_family = {next(iter(grads)): (slots, grads, mean)
                 for slots, grads, mean in pushes}
    (tslots_flat, hgrads, hmean), (cslots_flat, vgrads, vmean) = \
        by_family["h"], by_family["v"]
    assert hmean and vmean     # families carry raw sums + mean-norm flag
    tslots_flat, cslots_flat = np.asarray(tslots_flat), np.asarray(cslots_flat)
    gh, gv = np.asarray(hgrads["h"]), np.asarray(vgrads["v"])

    # numpy reference: recompute from the same sampled negatives
    # (target-slot layout: [center|negs] per pair)
    B, W2 = batch.contexts.shape
    K = model.negative
    d = model.len_vec
    t_slots = tslots_flat.reshape(B, W2, K + 1)
    sov = np.asarray(model._slot_of_vocab)
    h_tab = np.asarray(model.table.state["h"])
    v_tab = np.asarray(model.table.state["v"])
    alpha = model.alpha

    exp_err, n_valid = 0.0, 0
    # accumulate un-normalized grads per slot, then compare mean-normalized
    acc_h = {}
    acc_v = {}
    cnt_h = {}
    cnt_v = {}
    for b in range(B):
        for w in range(W2):
            if not batch.ctx_mask[b, w]:
                assert (t_slots[b, w] == -1).all()
                continue
            vs = sov[batch.contexts[b, w]]
            v_in = v_tab[vs]
            for k in range(K + 1):
                ts = t_slots[b, w, k]
                if ts < 0:
                    continue
                label = 1.0 if k == 0 else 0.0
                f = float(v_in @ h_tab[ts])
                f = np.clip(f, -6.0, 6.0)
                sig = 1.0 / (1.0 + np.exp(-f))
                g = (label - sig) * alpha
                exp_err += 1e4 * g * g
                n_valid += 1
                acc_h[ts] = acc_h.get(ts, 0) + g * v_in
                cnt_h[ts] = cnt_h.get(ts, 0) + 1
                acc_v[vs] = acc_v.get(vs, 0) + g * h_tab[ts]
            cnt_v[vs] = cnt_v.get(vs, 0) + 1

    assert n_valid == ec
    np.testing.assert_allclose(exp_err, es, rtol=2e-3)
    # scatter-summed device grads per slot, one push per family
    dev_h = {}
    dev_v = {}
    for i, s in enumerate(tslots_flat):
        if s >= 0:
            dev_h[s] = dev_h.get(s, 0) + gh[i]
    for i, s in enumerate(cslots_flat):
        if s >= 0:
            dev_v[s] = dev_v.get(s, 0) + gv[i]
    # device grads are RAW per-contribution values now; the 1/count mean
    # normalization happens inside transfer.push (mean=True flag above)
    for s, a in acc_h.items():
        np.testing.assert_allclose(dev_h[s], a, rtol=2e-3, atol=1e-6)
    for s, a in acc_v.items():
        np.testing.assert_allclose(dev_v[s], a, rtol=2e-3, atol=1e-6)


def test_w2v_table_survives_mid_train_abort(devices8):
    """The sync step donates its state input; the table must repoint at
    live buffers every step so an abnormal exit never strands the model
    with deleted arrays."""
    corpus = synthetic_corpus(20, vocab_size=40, length=12, seed=9)
    model = make_model()
    model.build(corpus)
    batcher = CBOWBatcher(corpus, model.vocab, model.window)

    class Boom(Exception):
        pass

    def exploding_epoch(batch_size):
        for i, b in enumerate(batcher.epoch(batch_size)):
            if i == 2:
                raise Boom
            yield b

    broken = type("B", (), {"epoch": staticmethod(exploding_epoch)})()
    with pytest.raises(Boom):
        model.train(batcher=broken, niters=1, batch_size=32)
    # every field still readable after the abort
    for f, arr in model.table.state.items():
        np.asarray(arr)
    k = int(model.vocab.keys[0])
    assert model.embedding(k) is not None


def test_w2v_async_local_steps_trains(devices8):
    corpus = synthetic_corpus(40, vocab_size=60, length=14, seed=8)
    model = make_model(word2vec={"local_steps": 3})
    losses = model.train(corpus, niters=4, batch_size=64)
    assert losses[-1] < losses[0], losses


def test_subsampling_keeps_dropped_words_in_contexts():
    # Reference word2vec.h:561: to_sample gates only the center position;
    # a heavily-subsampled frequent word must still appear as context.
    rng = np.random.default_rng(1)
    corpus = []
    for _ in range(10):
        sent = rng.integers(2, 12, size=10).tolist()
        interleaved = []
        for w in sent:  # word 1 between every pair -> ~50% of tokens
            interleaved += [1, int(w)]
        corpus.append(interleaved)
    vocab = build_vocab(corpus)
    # keep(word1) ~ 0.14, keep(others) = 1 at sample=0.01
    b = CBOWBatcher(corpus, vocab, window=2, sample=0.01, seed=0)
    batches = list(b.epoch(64))
    freq_idx = vocab.index[1]
    centers = np.concatenate([bt.centers[:bt.n_words] for bt in batches])
    ctx = np.concatenate(
        [bt.contexts[bt.ctx_mask].ravel() for bt in batches])
    # word 1's context share stays at its raw corpus share (~0.5) while
    # its center share is pushed well below it by the subsample gate —
    # under the wrong (sentence-filtering) semantics both would drop.
    center_frac = (centers == freq_idx).mean()
    ctx_frac = (ctx == freq_idx).mean()
    assert ctx_frac > 0.4, ctx_frac
    assert center_frac < ctx_frac - 0.1, (center_frac, ctx_frac)


def test_w2v_cli_rejects_bad_variant(tmp_path):
    from swiftmpi_tpu.apps.w2v_main import main
    data = tmp_path / "d.txt"
    data.write_text("1 2 3\n")
    assert main(["w2v", "-data", str(data), "-variant", "asnyc"]) == 1


def test_w2v_cli(tmp_path, devices8):
    from swiftmpi_tpu.apps.w2v_main import main
    corpus = synthetic_corpus(20, vocab_size=30, length=10, seed=6)
    data = tmp_path / "corpus.txt"
    with open(data, "w") as f:
        for sent in corpus:
            f.write(" ".join(map(str, sent)) + "\n")
    conf = tmp_path / "w2v.conf"
    conf.write_text("[word2vec]\nlen_vec: 8\nwindow: 2\nnegative: 3\n"
                    "min_sentence_length: 2\n[worker]\nminibatch: 128\n")
    out = str(tmp_path / "emb.txt")
    assert main(["w2v", "-config", str(conf), "-data", str(data),
                 "-niters", "1", "-output", out]) == 0
    assert len(open(out).readlines()) == 30


def test_w2v_resume_after_grow_invalidates_step(tmp_path, devices8):
    """resume() loading a post-grow() checkpoint must rebuild the jitted
    step: the old one bakes the smaller capacity into its mean-scale
    scatter, silently mis-normalizing rows in the grown region."""
    corpus = synthetic_corpus(30, vocab_size=60, length=12, seed=9)
    donor = make_model()
    donor.train(corpus, niters=1, batch_size=64)
    donor.table.grow()
    path = str(tmp_path / "ckpt")
    from swiftmpi_tpu.io.checkpoint import save_checkpoint
    save_checkpoint(donor.table, path, extra={"iter": np.int64(1)})

    model = make_model()
    model.build(corpus)
    model.train(corpus, niters=1, batch_size=64)
    assert model._step is not None
    old_cap = model.table.capacity
    assert model.resume(path) == 1
    assert model.table.capacity > old_cap    # checkpoint grew the table
    assert model._step is None               # stale step invalidated
    losses = model.train(corpus, niters=1, batch_size=64,
                         start_iter=1)
    assert np.isfinite(losses).all()


# -- async modes (word2vec_global.h:577-651) ------------------------------

@pytest.mark.slow
def test_w2v_hogwild_trains_and_matches_sync_loss(devices8):
    """Genuinely unsynchronized mode: 8 independent worker replicas,
    sequential arrival-order reconciliation.  Must converge, and land
    near the sync
    mode's final loss on the same corpus."""
    corpus = synthetic_corpus(150, vocab_size=50, length=12, seed=4)

    sync = make_model()
    sync_losses = sync.train(corpus, niters=3, batch_size=16)

    hw = make_model(word2vec={"async_mode": "hogwild"})
    hw_losses = hw.train(corpus, niters=3, batch_size=16)

    assert hw_losses[-1] < hw_losses[0]
    assert abs(hw_losses[-1] - sync_losses[-1]) / sync_losses[-1] < 0.3, (
        hw_losses, sync_losses)
    # the reconciled table must actually have moved every field family
    st = hw.table.state
    assert float(jnp.abs(st["h2sum"]).sum()) > 0
    assert float(jnp.abs(st["v2sum"]).sum()) > 0


@pytest.mark.slow
def test_w2v_staleness_sweep(devices8):
    """VERDICT round-1 item 5: loss vs staleness.  local_steps in
    {1, 4, 16} (snapshot mode) and hogwild: all variants must converge
    on the same corpus, with final losses in a band around sync —
    demonstrating where bounded staleness matches the reference's
    unsynchronized semantics."""
    corpus = synthetic_corpus(150, vocab_size=50, length=12, seed=11)
    finals = {}
    for name, overrides in (
            ("sync", {}),
            ("stale4", {"local_steps": 4}),
            ("stale16", {"local_steps": 16}),
            ("hogwild4", {"async_mode": "hogwild", "local_steps": 4})):
        m = make_model(word2vec=overrides)
        losses = m.train(corpus, niters=3, batch_size=16)
        assert losses[-1] < losses[0], (name, losses)
        # the final loss must BE the trajectory minimum: the fixed
        # delta-psum overstep bug's signature was late divergence
        # (4.41 -> 4.59 -> 6.05 — final 37% above the minimum), which a
        # final-vs-initial check alone cannot catch
        assert losses[-1] <= min(losses) + 1e-9, (name, losses)
        finals[name] = losses[-1]
    base = finals["sync"]
    for name, f in finals.items():
        if name == "hogwild4":
            # hogwild's staleness here is extreme for the corpus: a
            # reconciliation round = 8 workers x 4 batches = 32 stale
            # batches, ~1/3 of the whole epoch — correct sequential-
            # apply semantics converge strictly but measurably slower
            # at 3 epochs (the parity soak shows the trajectory closing
            # epoch over epoch; the old delta-sum reconciliation looked
            # "closer" at tiny scale only because its n_workers-fold
            # overstep accelerated early descent before diverging).
            assert abs(f - base) / base < 0.75, finals
        else:
            assert abs(f - base) / base < 0.35, finals


def test_w2v_hogwild_guards(devices8):
    corpus = synthetic_corpus(150, vocab_size=50, length=12, seed=4)
    # transfer=tpu cannot nest inside the per-worker mesh: clear error
    m = make_model(word2vec={"async_mode": "hogwild"},
                   cluster={"transfer": "tpu"})
    with pytest.raises(ValueError, match="transfer: xla"):
        m.train(corpus, niters=1, batch_size=16)
    # an epoch that can't fill one worker group must raise, not silently
    # report 0.0 loss
    m2 = make_model(word2vec={"async_mode": "hogwild", "local_steps": 64})
    with pytest.raises(RuntimeError, match="dispatched NO group"):
        m2.train(corpus, niters=1, batch_size=64)


def test_w2v_shared_negatives_trains(devices8):
    """TPU-first opt-in (shared_negatives: 1): one weighted pool of
    negatives shared by the batch, MXU-matmul NS math.  The error terms
    carry the gradients' negative/K weighting (advisor r04), so the
    reported loss is SCALE-comparable with parity mode — pinned here —
    while the pool sampling still converges differently at toy scale
    (embedding quality is the co-occurrence test below)."""
    corpus = synthetic_corpus(150, vocab_size=50, length=12, seed=9)
    parity = make_model()
    parity_losses = parity.train(corpus, niters=1, batch_size=128)
    fast = make_model(word2vec={"shared_negatives": 1, "shared_pool": 256})
    fast_losses = fast.train(corpus, niters=8, batch_size=128)
    # same loss scale as parity mode (the weighting's whole point): the
    # old unweighted metric sat ~K/negative = 85x below it
    assert abs(fast_losses[0] - parity_losses[0]) < 0.15 * parity_losses[0], \
        (fast_losses[0], parity_losses[0])
    assert fast_losses[-1] < fast_losses[0], fast_losses
    assert min(fast_losses) < 0.9 * fast_losses[0], fast_losses


def test_w2v_shared_negatives_cooccurrence(devices8):
    rng = np.random.default_rng(0)
    topic_a = list(range(1, 21))
    topic_b = list(range(21, 41))
    corpus = [[int(w) for w in rng.choice(
        topic_a if i % 2 == 0 else topic_b, size=12)] for i in range(120)]
    model = make_model(word2vec={"shared_negatives": 1,
                                 "shared_pool": 256})
    model.train(corpus, niters=8, batch_size=128)

    def vec(k):
        v = model.embedding(k)
        return v / (np.linalg.norm(v) + 1e-9)

    within = np.mean([vec(topic_a[i]) @ vec(topic_a[j])
                      for i in range(5) for j in range(5) if i != j])
    across = np.mean([vec(topic_a[i]) @ vec(topic_b[j])
                      for i in range(5) for j in range(5)])
    assert within > across, (within, across)


def test_w2v_shared_negatives_grads_match_numpy(devices8):
    """Golden check of the shared-pool gradient phase, including the
    center/pool overlap case: a key that appears many times as a center
    AND in the pool must get its full summed negative row (sum
    semantics), not one attenuated by the center occurrence count."""
    from swiftmpi_tpu.ops.sampling import sample_alias

    model = make_model(word2vec={"shared_negatives": 1, "shared_pool": 16,
                                 "negative": 4, "len_vec": 8, "window": 2})
    corpus = synthetic_corpus(10, vocab_size=30, length=10, seed=5)
    model.build(corpus)
    model.stencil = 0      # drives the per-pair builders itself
    B, W2 = 24, 4
    V = len(model.vocab)
    rng = np.random.default_rng(2)
    # one dominant center (vocab idx 0) repeated: the overlap trap
    centers = np.zeros(B, np.int32)
    centers[12:] = rng.integers(0, V, size=12)
    contexts = rng.integers(0, V, size=(B, W2)).astype(np.int32)
    mask = np.ones((B, W2), bool)
    key = jax.random.key(11)

    grads_fn = jax.jit(model._build_grads())
    pushes, es, ec = grads_fn(
        model.table.state, model._slot_of_vocab, model._alias_prob,
        model._alias_idx, jnp.asarray(centers), jnp.asarray(contexts),
        jnp.asarray(mask), key)
    ((pos_slots, pos_g, pos_mean), (neg_slots, neg_g, neg_mean),
     (ctx_slots, ctx_g, ctx_mean)) = pushes
    # positives/contexts mean-normalize in the push; the pool keeps SUM
    assert pos_mean and ctx_mean and not neg_mean

    # numpy recomputation with the same drawn pool
    K = model.shared_pool
    negs = np.asarray(sample_alias(key, model._alias_prob,
                                   model._alias_idx, (K,)))
    sov = np.asarray(model._slot_of_vocab)
    h = np.asarray(model.table.state["h"])
    v = np.asarray(model.table.state["v"])
    alpha, ratio = model.alpha, model.negative / K
    neu1 = v[sov[contexts]].sum(axis=1)                      # (B, d)
    sig = lambda f: 1.0 / (1.0 + np.exp(-np.clip(f, -6, 6)))

    want_neg = np.zeros((K, 8))
    for k in range(K):
        gsum = np.zeros(8)
        for b in range(B):
            if negs[k] == centers[b]:
                continue
            f = float(neu1[b] @ h[sov[negs[k]]])
            f = np.clip(f, -6.0, 6.0)
            g = (0.0 - (0.0 if f < -6 else sig(f))) * alpha
            gsum += g * ratio * neu1[b]
        want_neg[k] = gsum
    np.testing.assert_allclose(np.asarray(neg_g["h"]), want_neg,
                               rtol=2e-3, atol=1e-6)
    # slot masking mirrors production: a pool key is dead (-1) only when
    # it equals EVERY center in the batch; otherwise its slot passes
    # through un-attenuated (sum semantics, no 1/center_count)
    k_alive = np.array([(negs[k] != centers).any() for k in range(K)])
    np.testing.assert_array_equal(np.asarray(neg_slots),
                                  np.where(k_alive, sov[negs], -1))

    # positive rows: raw per-contribution grads (the 1/center_count mean
    # lands inside transfer.push via the mean=True flag)
    want_pos = np.zeros((B, 8))
    for b in range(B):
        f = np.clip(float(neu1[b] @ h[sov[centers[b]]]), -6, 6)
        g = (1.0 - sig(f)) * alpha
        want_pos[b] = g * neu1[b]
    np.testing.assert_allclose(np.asarray(pos_g["h"]), want_pos,
                               rtol=2e-3, atol=1e-6)


def test_w2v_sg_shared_trains(devices8):
    """Skip-gram + shared pool (sg: 1, shared_negatives: 1): the
    TPU-first rendering of BASELINE config #2 — target gather collapses
    from B*2W*(K+1) rows to B + pool (round-3 verdict Weak #6)."""
    corpus = synthetic_corpus(150, vocab_size=50, length=12, seed=9)
    model = make_model(word2vec={"sg": 1, "shared_negatives": 1,
                                 "shared_pool": 256})
    model.build(corpus)
    losses = model.train(corpus, niters=4, batch_size=128)
    assert model.resolved_rendering == "sg_shared"
    assert losses[-1] < losses[0], losses


def test_w2v_sg_shared_cooccurrence(devices8):
    rng = np.random.default_rng(0)
    topic_a = list(range(1, 21))
    topic_b = list(range(21, 41))
    corpus = [[int(w) for w in rng.choice(
        topic_a if i % 2 == 0 else topic_b, size=12)] for i in range(120)]
    model = make_model(word2vec={"sg": 1, "shared_negatives": 1,
                                 "shared_pool": 256})
    model.train(corpus, niters=8, batch_size=128)

    def vec(k):
        v = model.embedding(k)
        return v / (np.linalg.norm(v) + 1e-9)

    within = np.mean([vec(topic_a[i]) @ vec(topic_a[j])
                      for i in range(5) for j in range(5) if i != j])
    across = np.mean([vec(topic_a[i]) @ vec(topic_b[j])
                      for i in range(5) for j in range(5)])
    assert within > across, (within, across)


def test_w2v_sg_shared_grads_match_numpy(devices8):
    """Golden check of the sg shared-pool gradient phase: per-PAIR
    positive grads (mean-normalized at push), one summed pool family
    (no mean attenuation), per-pair v grads from both terms."""
    model = make_model(word2vec={"sg": 1, "shared_negatives": 1,
                                 "shared_pool": 16, "negative": 4,
                                 "len_vec": 8, "window": 2})
    corpus = synthetic_corpus(10, vocab_size=30, length=10, seed=5)
    model.build(corpus)
    model.stencil = 0      # drives the per-pair builders itself
    B, W2 = 24, 4
    V = len(model.vocab)
    rng = np.random.default_rng(2)
    centers = np.zeros(B, np.int32)
    centers[12:] = rng.integers(0, V, size=12)
    contexts = rng.integers(0, V, size=(B, W2)).astype(np.int32)
    mask = np.ones((B, W2), bool)
    mask[3, 1:] = False                       # padded pairs must be dead
    key = jax.random.key(11)

    grads_fn = jax.jit(model._build_grads())
    pushes, es, ec = grads_fn(
        model.table.state, model._slot_of_vocab, model._alias_prob,
        model._alias_idx, jnp.asarray(centers), jnp.asarray(contexts),
        jnp.asarray(mask), key)
    ((pos_slots, pos_g, pos_mean), (neg_slots, neg_g, neg_mean),
     (ctx_slots, ctx_g, ctx_mean)) = pushes
    assert pos_mean and ctx_mean and not neg_mean

    K = model.shared_pool
    negs = np.asarray(sample_alias(key, model._alias_prob,
                                   model._alias_idx, (K,)))
    sov = np.asarray(model._slot_of_vocab)
    h = np.asarray(model.table.state["h"])
    v = np.asarray(model.table.state["v"])
    alpha, ratio = model.alpha, model.negative / K
    d = 8
    sig = lambda f: 1.0 / (1.0 + np.exp(-np.clip(f, -6, 6)))

    v_in = v[sov[contexts]]                                  # (B, W2, d)
    want_pos = np.zeros((B, W2, d))
    want_neg = np.zeros((K, d))
    want_ctx = np.zeros((B, W2, d))
    for b in range(B):
        h_c = h[sov[centers[b]]]
        for w in range(W2):
            if not mask[b, w]:
                continue
            g_pos = (1.0 - sig(float(v_in[b, w] @ h_c))) * alpha
            want_pos[b, w] = g_pos * v_in[b, w]
            want_ctx[b, w] = g_pos * h_c
            for k in range(K):
                if negs[k] == centers[b]:
                    continue
                g = (0.0 - sig(float(v_in[b, w] @ h[sov[negs[k]]]))) \
                    * alpha * ratio
                want_neg[k] += g * v_in[b, w]
                want_ctx[b, w] += g * h[sov[negs[k]]]
    np.testing.assert_allclose(np.asarray(pos_g["h"]),
                               want_pos.reshape(-1, d),
                               rtol=2e-3, atol=1e-6)
    np.testing.assert_allclose(np.asarray(neg_g["h"]), want_neg,
                               rtol=2e-3, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ctx_g["v"]),
                               want_ctx.reshape(-1, d),
                               rtol=2e-3, atol=1e-6)
    # dead pair slots are masked out of the positive/context families
    assert np.asarray(pos_slots).reshape(B, W2)[3, 1] == -1
    assert np.asarray(ctx_slots).reshape(B, W2)[3, 1] == -1


def test_w2v_bfloat16_table_trains_and_roundtrips(tmp_path, devices8):
    """[server] dtype: bfloat16 — embedding fields stored at half width
    (the TPU gather/scatter bytes), math in fp32, accumulators fp32."""
    corpus = synthetic_corpus(60, vocab_size=100, length=18, seed=2)
    model = make_model(server={"dtype": "bfloat16"})
    losses = model.train(corpus, niters=4, batch_size=128)
    assert losses[-1] < losses[0], losses
    assert model.table.state["h"].dtype == jnp.bfloat16
    assert model.table.state["h2sum"].dtype == jnp.float32

    # text checkpoint roundtrip keeps values to bf16 resolution
    path = str(tmp_path / "emb16.txt")
    model.save(path)
    model2 = make_model(server={"dtype": "bfloat16"})
    model2._capacity_per_shard = model.table.key_index.capacity_per_shard
    model2.load(path)
    k = int(model.vocab.keys[0])
    np.testing.assert_allclose(
        np.asarray(model.embedding(k), np.float32),
        np.asarray(model2.embedding(k), np.float32), rtol=1e-2, atol=1e-3)

    # fp32 and bf16 runs track each other at test scale
    base = make_model().train(corpus, niters=4, batch_size=128)
    assert abs(losses[-1] - base[-1]) / base[-1] < 0.1, (losses, base)


def test_w2v_bfloat16_npz_checkpoint_resume(tmp_path, devices8):
    """npz (full-fidelity) checkpoint path with bf16 storage: np.savez
    has no bfloat16, so fields round-trip via exact fp32 upcast and are
    restored to the table dtype bit-identically."""
    corpus = synthetic_corpus(30, vocab_size=40, length=12, seed=6)
    model = make_model(server={"dtype": "bfloat16"})
    ckpt = str(tmp_path / "w2v16")
    model.train(corpus, niters=2, batch_size=64, checkpoint_path=ckpt)
    before = {f: np.asarray(v, np.float32)
              for f, v in model.table.state.items()}

    model2 = make_model(server={"dtype": "bfloat16"})
    model2.build(corpus)
    it = model2.resume(ckpt)
    assert it == 2
    assert model2.table.state["h"].dtype == jnp.bfloat16
    for f, want in before.items():
        np.testing.assert_array_equal(
            np.asarray(model2.table.state[f], np.float32), want)
    # and training continues from the restored state
    losses = model2.train(corpus, niters=1, batch_size=64)
    assert np.isfinite(losses[0])


class _ShortTailBatcher:
    """Wraps CBOWBatcher but truncates the final batch to an odd shape —
    the in-repo batchers always pad to batch_size, so this is the only
    way to exercise the fused loop's mid-epoch single-dispatch fallback."""

    def __init__(self, inner):
        self.inner = inner

    def epoch(self, batch_size):
        batches = list(self.inner.epoch(batch_size))
        for b in batches[:-1]:
            yield b
        last = batches[-1]
        n = max(1, batch_size // 2)
        import swiftmpi_tpu.data.text as text
        yield text.CBOWBatch(last.centers[:n], last.contexts[:n],
                             last.ctx_mask[:n], min(last.n_words, n))


@pytest.mark.slow
def test_w2v_fused_inner_steps_trains_like_per_batch(devices8):
    """[worker] inner_steps: N sync steps fused per dispatch via
    lax.scan.  Same math and update order as the per-batch loop (only
    the RNG key schedule differs), so the loss trajectory must track the
    unfused run closely — including a genuinely odd-shaped tail batch,
    which flushes the pending group through single dispatches."""
    corpus = synthetic_corpus(90, vocab_size=60, length=12, seed=8)
    base = make_model()
    base_losses = base.train(corpus, niters=3, batch_size=64)

    fused = make_model(worker={"inner_steps": 4})
    fused_losses = fused.train(corpus, niters=3, batch_size=64)
    assert fused_losses[-1] < fused_losses[0]
    for a, b in zip(fused_losses, base_losses):
        assert abs(a - b) / b < 0.2, (fused_losses, base_losses)

    odd = make_model(worker={"inner_steps": 4})
    odd.build(corpus)
    batcher = _ShortTailBatcher(
        CBOWBatcher(corpus, odd.vocab, odd.window, seed=2008))
    odd_losses = odd.train(batcher=batcher, niters=3, batch_size=64)
    assert odd_losses[-1] < odd_losses[0]
    for a, b in zip(odd_losses, base_losses):
        assert abs(a - b) / b < 0.25, (odd_losses, base_losses)


def test_w2v_partial_tail_group_fuses(devices8):
    """A small corpus whose epoch never fills a full inner_steps group
    must still fuse its tail into ONE scan dispatch (per-batch tail
    dispatches each pay the per-dispatch overhead).  Pin the per-length compile cache and loss sanity."""
    corpus = synthetic_corpus(20, vocab_size=60, length=12, seed=8)
    model = make_model(worker={"inner_steps": 8})
    model.build(corpus)
    losses = model.train(corpus, niters=3, batch_size=64)
    assert losses[-1] < losses[0], losses
    # epoch = a few full 64-center batches + an odd tail: the full
    # batches fused at SOME length < inner_steps, and no 8-length
    # program was ever compiled
    lens = set(model._fused_cache)
    assert lens and all(1 < n < 8 for n in lens), lens
    # baseline parity: same trajectory as the unfused loop
    base = make_model()
    base_losses = base.train(corpus, niters=3, batch_size=64)
    for a, b in zip(losses, base_losses):
        assert abs(a - b) / b < 0.25, (losses, base_losses)


def test_w2v_cli_hogwild_variant(tmp_path, devices8):
    from swiftmpi_tpu.apps.w2v_main import main
    from swiftmpi_tpu.utils.config import global_config
    corpus = synthetic_corpus(300, vocab_size=40, length=10, seed=6)
    data = tmp_path / "corpus.txt"
    with open(data, "w") as f:
        for sent in corpus:
            f.write(" ".join(map(str, sent)) + "\n")
    conf = tmp_path / "w2v.conf"
    conf.write_text("[word2vec]\nlen_vec: 8\nwindow: 2\nnegative: 3\n"
                    "min_sentence_length: 2\n[worker]\nminibatch: 128\n")
    out = str(tmp_path / "embhw.txt")
    try:
        assert main(["w2v", "-config", str(conf), "-data", str(data),
                     "-variant", "hogwild", "-niters", "1",
                     "-output", out]) == 0
    finally:
        global_config().clear()
    assert len(open(out).readlines()) == 40


@pytest.mark.slow
def test_w2v_hogwild_reconciliation_is_exact_worker_major_apply(devices8):
    """The ring-state reconciliation (state travels, pushes stay local)
    must produce BIT-level the same table as the literal worker-major
    sequential replay: base, then every push of worker 0 in step order,
    then worker 1's, ...  — the semantics the docstring promises and the
    round-2 all_gather rendering computed directly."""
    corpus = synthetic_corpus(200, vocab_size=60, length=12, seed=21)
    n_inner = 2
    m = make_model(word2vec={"async_mode": "hogwild",
                             "local_steps": n_inner})
    m.build(corpus)
    step, n_workers = m._build_hogwild_step(n_inner)

    B = 16
    batcher = CBOWBatcher(corpus, m.vocab, m.window, m.sample, seed=9)
    group = []
    for b in batcher.epoch(B):
        if len(b.centers) == B:
            group.append(b)
        if len(group) == 8 * n_inner:
            break
    assert len(group) == 8 * n_inner
    c = jnp.asarray(np.stack([np.asarray(b.centers) for b in group]))
    x = jnp.asarray(np.stack([np.asarray(b.contexts) for b in group]))
    mk = jnp.asarray(np.stack([np.asarray(b.ctx_mask) for b in group]))
    key = jax.random.key(42)
    base = {f: np.asarray(v).copy() for f, v in m.table.state.items()}

    # manual worker-major replay with the same per-worker streams
    grads_fn = m._build_grads()
    apply_fn = m._build_apply()
    sov, ap, ai = m._slot_of_vocab, m._alias_prob, m._alias_idx
    all_pushes = []
    sub = jax.random.split(key)[1]     # the step splits the key it is given
    for w in range(8):
        keys = jax.random.split(jax.random.fold_in(sub, w), n_inner)
        local = {f: jnp.asarray(v) for f, v in base.items()}
        seq = []
        for s in range(n_inner):
            i = w * n_inner + s
            pushes, es, ec = grads_fn(local, sov, ap, ai,
                                      c[i], x[i], mk[i], keys[s])
            local = apply_fn(local, pushes)
            seq.append(pushes)
        all_pushes.append(seq)
    ref = {f: jnp.asarray(v) for f, v in base.items()}
    for w in range(8):
        for s in range(n_inner):
            ref = apply_fn(ref, all_pushes[w][s])

    got, _key, _tally, es = step(
        {f: jnp.asarray(v) for f, v in base.items()},
        sov, ap, ai, c, x, mk, key)
    for f in ref:
        # jit-fused vs eager replay differ only by float reassociation
        # (~1e-7); a wrong APPLY ORDER shows up at ~1e-2 (AdaGrad
        # accumulator ordering), far outside this tolerance
        np.testing.assert_allclose(np.asarray(got[f]), np.asarray(ref[f]),
                                   rtol=1e-4, atol=1e-6, err_msg=f)


def test_multi_step_scan_matches_single_steps(devices8):
    import jax
    from swiftmpi_tpu.data.text import CBOWBatcher, synthetic_corpus
    from swiftmpi_tpu.models import Word2Vec
    from swiftmpi_tpu.utils import ConfigParser

    cfg = ConfigParser().update({
        "cluster": {"transfer": "xla"},
        "word2vec": {"len_vec": 8, "window": 2, "negative": 3,
                     "sample": -1, "learning_rate": 0.05},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 128},
    })
    corpus = synthetic_corpus(20, vocab_size=40, length=12, seed=9)
    model = Word2Vec(config=cfg)
    model.build(corpus)
    model.stencil = 0      # drives the per-pair builders itself
    batches = list(CBOWBatcher(corpus, model.vocab, 2).epoch(64))[:2]
    import jax.numpy as jnp
    centers = jnp.stack([jnp.asarray(b.centers) for b in batches])
    contexts = jnp.stack([jnp.asarray(b.contexts) for b in batches])
    masks = jnp.stack([jnp.asarray(b.ctx_mask) for b in batches])

    multi = model._build_multi_step(2)
    key = jax.random.key(7)
    # deep-copy: multi donates its state argument
    state_copy = {f: jnp.array(v) for f, v in model.table.state.items()}
    # the program splits the key it is given, then once a step
    sub = jax.random.split(key)[1]
    s_multi, *_sums = multi(
        state_copy, model._slot_of_vocab, model._alias_prob,
        model._alias_idx, centers, contexts, masks, key)

    grads_fn = jax.jit(model._build_grads())
    apply_fn = jax.jit(model._build_apply())
    s = dict(model.table.state)
    keys = jax.random.split(sub, 2)
    for i in range(2):
        pushes, _, _ = grads_fn(
            s, model._slot_of_vocab, model._alias_prob, model._alias_idx,
            centers[i], contexts[i], masks[i], keys[i])
        s = apply_fn(s, pushes)
    for f in s:
        np.testing.assert_allclose(np.asarray(s[f]),
                                   np.asarray(s_multi[f]),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("section, key, value", [
    ("cluster", "data_plane", "pallas"),
    ("word2vec", "dense_logits", 1),
], ids=["data_plane", "dense_logits"])
def test_removed_keys_do_not_fork_the_step(section, key, value, devices8):
    """A conf that still carries a key ISSUE 45 removed lowers the step
    of a conf without it: nothing reads the key any more."""
    from tests.test_program_choice import lowered_steps

    def conf(**extra):
        return ConfigParser().update({
            "cluster": {"transfer": "xla"},
            "word2vec": {"len_vec": 16, "window": 2, "negative": 5,
                         "sample": -1, "learning_rate": 0.05},
            "server": {"initial_learning_rate": 0.3},
            "worker": {"minibatch": 512}, **extra})

    base = conf()
    carrying = conf()
    carrying.set(section, key, value)
    want, got = lowered_steps(base), lowered_steps(carrying)
    assert sorted(got) == sorted(want) == ["pairs", "spans"]
    assert got == want
