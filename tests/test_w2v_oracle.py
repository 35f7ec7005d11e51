"""Loss-parity oracle tests: the JAX CBOW step vs a sequential numpy port
of the reference training loop (swiftmpi_tpu/testing/w2v_oracle.py).

Closes the round-1 test asymmetry: skip-gram had a numpy cross-check
(test_word2vec.py::test_w2v_skipgram_grads_match_numpy) but the CBOW hot
loop — the reference's actual ``learn_instance``
(/root/reference/src/apps/word2vec/word2vec.h:550-615) — was only tested
for loss-decrease and co-occurrence structure.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from swiftmpi_tpu.models.word2vec import Word2Vec  # noqa: E402
from swiftmpi_tpu.ops.sampling import sample_alias  # noqa: E402
from swiftmpi_tpu.testing import (W2VOracle, cbow_batch_grads,  # noqa: E402
                                  exp_table_sigmoid, gen_unigram_table)
from swiftmpi_tpu.utils import ConfigParser  # noqa: E402


def make_model(**overrides):
    cfg = ConfigParser().update({
        "cluster": {"server_num": 2, "transfer": "xla"},
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


def corpus(n_sent=40, vocab=30, length=12, seed=0):
    """Deterministic corpus over keys 1..vocab (0 is excluded: the
    reference redraws negative samples that hit key 0 — word2vec.h:581-583
    — a quirk the parity run avoids by construction)."""
    rng = np.random.default_rng(seed)
    # zipf-ish over 1..vocab so the unigram table is non-trivial
    p = 1.0 / np.arange(1, vocab + 1)
    p /= p.sum()
    return [list(map(int, rng.choice(np.arange(1, vocab + 1), size=length,
                                     p=p)))
            for _ in range(n_sent)]


# -- exp table -------------------------------------------------------------

def test_exp_table_matches_exact_sigmoid_within_bucket():
    for f in np.linspace(-5.99, 5.99, 97):
        exact = 1.0 / (1.0 + np.exp(-f))
        assert abs(exp_table_sigmoid(float(f)) - exact) < 7e-3


def test_unigram_table_proportions():
    freq = {1: 100, 2: 10, 3: 1}
    table = gen_unigram_table(freq, table_size=100_000)
    pow_ = np.array([100.0, 10.0, 1.0]) ** 0.75
    want = pow_ / pow_.sum()
    got = np.array([(table == w).mean() for w in (1, 2, 3)])
    np.testing.assert_allclose(got, want, atol=1e-3)


# -- per-batch CBOW gradient parity ----------------------------------------

def _dense_grads_from_step(model, state, centers, contexts, ctx_mask, key):
    """Run the model's gradient phase and scatter its per-contribution
    grads into dense vocab-id space for comparison.  The gradient phase
    emits one push per family: (target_slots, {"h": ...}) and
    (context_slots, {"v": ...})."""
    grads_fn = model._build_grads()
    pushes, es, ec = grads_fn(
        state, model._slot_of_vocab, model._alias_prob, model._alias_idx,
        jnp.asarray(centers), jnp.asarray(contexts), jnp.asarray(ctx_mask),
        key)
    # invert slot -> vocab id (key); slots are unique per vocab entry
    slot_to_key = {}
    for k, i in zip(model.vocab.keys.tolist(),
                    np.asarray(model._slot_of_vocab).tolist()):
        slot_to_key[i] = int(k)
    V = int(model.vocab.keys.max()) + 1
    d = model.len_vec
    dense = {f: np.zeros((V, d), np.float64) for f in ("h", "v")}
    for slots_j, grads, mean in pushes:
        slots_np = np.asarray(slots_j).tolist()
        counts = {}
        for s in slots_np:
            if s >= 0:
                counts[s] = counts.get(s, 0) + 1
        for f, g in grads.items():
            g = np.asarray(g, np.float64)
            for j, s in enumerate(slots_np):
                if s >= 0:
                    # mean=True pushes carry raw sums; the transfer
                    # divides by the key's contribution count
                    dense[f][slot_to_key[s]] += (
                        g[j] / counts[s] if mean else g[j])
    return dense["h"], dense["v"], float(es), int(ec)


def test_w2v_cbow_grads_match_numpy(devices8):
    model = make_model()
    sents = corpus(seed=3)
    model.build(sents)
    model.stencil = 0      # drives the per-pair builders itself
    state = model.table.state
    W2, K, B = 2 * model.window, model.negative, 24

    rng = np.random.default_rng(1)
    centers = rng.integers(1, 30, size=B).astype(np.int32)
    contexts = rng.integers(1, 30, size=(B, W2)).astype(np.int32)
    ctx_mask = rng.random((B, W2)) < 0.8
    ctx_mask[0] = False          # one empty row: must contribute nothing
    ctx_mask[1] = True
    key = jax.random.key(7)

    got_h, got_v, es, ec = _dense_grads_from_step(
        model, state, centers, contexts, ctx_mask, key)

    # identical randomness: the exact negatives the step drew
    negs_v = np.asarray(sample_alias(key, model._alias_prob,
                                     model._alias_idx, (B, K)))
    negs = model.vocab.keys[negs_v].astype(np.int64)   # vocab idx -> key
    # dense rows in key space from the model's table
    V = int(model.vocab.keys.max()) + 1
    h = np.zeros((V, model.len_vec), np.float32)
    v = np.zeros((V, model.len_vec), np.float32)
    sov = np.asarray(model._slot_of_vocab)
    for kk, i in zip(model.vocab.keys.tolist(), sov.tolist()):
        h[int(kk)] = np.asarray(state["h"])[i]
        v[int(kk)] = np.asarray(state["v"])[i]
    ctx_keys = np.zeros_like(contexts, np.int64)
    ctx_keys[ctx_mask] = np.asarray(
        model.vocab.keys)[contexts[ctx_mask]].astype(np.int64)
    center_keys = model.vocab.keys[centers].astype(np.int64)

    # exact-sigmoid oracle: tight parity (same math, fp order aside)
    want_h, want_v, w_es, w_ec = cbow_batch_grads(
        h, v, center_keys, ctx_keys, ctx_mask, negs, model.alpha,
        quantized_sigmoid=False)
    assert ec == w_ec
    np.testing.assert_allclose(es, w_es, rtol=1e-4)
    np.testing.assert_allclose(got_h, want_h, atol=2e-6, rtol=1e-3)
    np.testing.assert_allclose(got_v, want_v, atol=2e-6, rtol=1e-3)

    # table-quantized oracle (the reference's actual sigmoid): deviation
    # bounded by the bucket error (~7e-3 in s, times alpha and |neu1|)
    qh, qv, q_es, q_ec = cbow_batch_grads(
        h, v, center_keys, ctx_keys, ctx_mask, negs, model.alpha,
        quantized_sigmoid=True)
    assert q_ec == ec
    assert abs(q_es - es) / max(es, 1e-9) < 0.05
    assert np.max(np.abs(qh - got_h)) < 1e-3
    assert np.max(np.abs(qv - got_v)) < 1e-3


# -- multi-epoch loss parity ----------------------------------------------

def test_loss_parity_vs_reference_oracle(devices8):
    """Same corpus, same hyperparameters, comparable batch granularity:
    the reference-faithful sequential oracle and the fused SPMD trainer
    must track the same loss trajectory (north-star clause 2)."""
    sents = corpus(n_sent=40, vocab=30, length=12, seed=3)
    niters = 4

    oracle = W2VOracle(len_vec=16, window=2, negative=5, alpha=0.05,
                       server_lr=0.3, sample=-1.0, minibatch_lines=10,
                       table_size=200_000, seed=2008, init_seed=0)
    ref_losses = oracle.train(sents, niters=niters)

    model = make_model()
    # 11 lines/batch x 12 tokens: match the oracle's update granularity
    losses = model.train(sents, niters=niters, batch_size=132)

    assert losses[-1] < losses[0], losses
    assert ref_losses[-1] < ref_losses[0], ref_losses
    # final loss parity within 12.5% relative (different RNG streams and
    # row inits; identical math otherwise)
    rel = abs(losses[-1] - ref_losses[-1]) / ref_losses[-1]
    assert rel < 0.125, (losses, ref_losses)
    # Trajectory parity from iter 1 on.  Iter 0 is dominated by the
    # first-update AdaGrad transient (first step ~= server_lr per element
    # regardless of gradient scale) and is measured to swing 37% across
    # the oracle's *own* sampling-LCG seeds (5.22..7.15 for seeds
    # {2008, 7} x init {0,1,2}); from iter 1 the spread collapses to ~4%,
    # so 25% is a real check there and meaningless at iter 0.
    assert losses[0] < 10.0 and ref_losses[0] < 10.0, (losses, ref_losses)
    for a, b in zip(losses[1:], ref_losses[1:]):
        assert abs(a - b) / b < 0.25, (losses, ref_losses)
