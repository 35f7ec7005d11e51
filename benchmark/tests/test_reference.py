"""The plain reference against a 20-row hand example: the per-center loop of
the reference's word2vec.h written out in Python floats."""

import math

import numpy as np
import pytest

from benchmark.reference import w2v as ref

ALPHA, LR = 0.05, 0.7
ROWS, D, B, K, W2 = 20, 4, 6, 3, 4


def example():
    rng = np.random.default_rng(0)
    rows = {"h": rng.normal(0, 1.0, (ROWS, D)).astype(np.float32),
            "v": rng.normal(0, 1.0, (ROWS, D)).astype(np.float32),
            "h2sum": rng.random((ROWS, D)).astype(np.float32) * 0.01,
            "v2sum": rng.random((ROWS, D)).astype(np.float32) * 0.01}
    rows["h"][3] *= 6.0                    # |f| > 6: the clip must show
    t_ids = rng.integers(0, ROWS, (B, K + 1))
    c_ids = rng.integers(0, ROWS, (B, W2))
    t_valid = np.ones((B, K + 1), bool)
    c_valid = rng.random((B, W2)) < 0.8
    t_ids[0] = [3, 3, 5, 3]                # duplicates inside one center
    t_valid[0, 1] = t_valid[0, 3] = False  # negatives equal to the center
    c_ids[1] = [7, 7, 7, 8]                # one key, three contributions
    c_valid[1] = True
    c_valid[2] = False                     # a center with no context
    t_valid[2] = False
    t_ids[4, 0] = 3
    return rows, t_ids, t_valid, c_ids, c_valid


def by_hand(rows, t_ids, t_valid, c_ids, c_valid):
    h, v = rows["h"].astype(np.float64), rows["v"].astype(np.float64)
    grads = {"h": np.zeros((ROWS, D)), "v": np.zeros((ROWS, D))}
    count = {"h": np.zeros(ROWS), "v": np.zeros(ROWS)}
    clipped = 0
    for b in range(B):
        ctx = [c_ids[b, j] for j in range(W2) if c_valid[b, j]]
        neu1 = sum((v[c] for c in ctx), np.zeros(D))
        neu1e = np.zeros(D)
        for k in range(K + 1):
            if not t_valid[b, k]:
                continue
            t, label = t_ids[b, k], 1.0 if k == 0 else 0.0
            f = float(neu1 @ h[t])
            if f > 6:
                g, clipped = (label - 1.0) * ALPHA, clipped + 1
            elif f < -6:
                g, clipped = (label - 0.0) * ALPHA, clipped + 1
            else:
                g = (label - 1.0 / (1.0 + math.exp(-f))) * ALPHA
            grads["h"][t] += g * neu1
            count["h"][t] += 1
            neu1e += g * h[t]
        for c in ctx:
            grads["v"][c] += neu1e
            count["v"][c] += 1
    out = {}
    for f, acc in (("h", "h2sum"), ("v", "v2sum")):
        g = grads[f] / np.maximum(count[f], 1)[:, None]
        a = rows[acc] + g * g
        out[acc] = a
        out[f] = rows[f] + LR * g / np.sqrt(a + 1e-6)
    return out, count, clipped


def test_step_matches_the_hand_loop():
    rows, t_ids, t_valid, c_ids, c_valid = example()
    want, count, clipped = by_hand(rows, t_ids, t_valid, c_ids, c_valid)
    assert clipped >= 1 and count["v"][7] >= 3 and count["h"][3] >= 2
    got = ref.step(rows, t_ids, t_valid, c_ids, c_valid, ALPHA, LR)
    for f in want:
        assert got[f].dtype == np.float32
        np.testing.assert_allclose(got[f], want[f], rtol=2e-5, atol=1e-7)
        untouched = count[f[0]] == 0
        assert untouched.any()
        assert np.array_equal(got[f][untouched], rows[f][untouched])


def test_tolerance_catches_bf16_rows_and_a_dropped_duplicate():
    import jax.numpy as jnp

    rows, t_ids, t_valid, c_ids, c_valid = example()
    want = ref.step(rows, t_ids, t_valid, c_ids, c_valid, ALPHA, LR)
    same = ref.compare(want, want, rows)
    assert all(f["ok"] and f["max_err"] == 0 for f in same.values())
    bf16 = {f: np.asarray(jnp.asarray(a).astype(jnp.bfloat16)
                          .astype(jnp.float32)) for f, a in want.items()}
    assert not ref.compare(bf16, want, rows)["v"]["ok"]
    dropped = c_valid.copy()
    dropped[1, 0] = False                  # one of key 7's three
    got = ref.step(rows, t_ids, t_valid, c_ids, dropped, ALPHA, LR)
    assert not ref.compare(got, want, rows)["v"]["ok"]
    nan = {f: a.copy() for f, a in want.items()}
    nan["h"][0, 0] = np.nan
    assert not ref.compare(nan, want, rows)["h"]["ok"]


def test_held_out_loss_by_hand():
    h = np.array([[1.0, 0.0], [0.0, 2.0], [1.0, 1.0]], np.float32)
    v = np.array([[0.5, 0.5], [1.0, -1.0]], np.float32)
    # one center: contexts v0 + v1 = (1.5, -0.5); targets h0 (+), h1 (-);
    # the third target is masked
    t_ids, t_valid = np.array([[0, 1, 2]]), np.array([[True, True, False]])
    c_ids, c_valid = np.array([[0, 1]]), np.array([[True, True]])
    f_pos, f_neg = 1.5, -1.0
    sig = lambda x: 1.0 / (1.0 + math.exp(-x))          # noqa: E731
    want_ns = math.log1p(math.exp(-f_pos)) + math.log1p(math.exp(f_neg))
    want_err = 1e4 * ALPHA ** 2 * ((1 - sig(f_pos)) ** 2
                                   + sig(f_neg) ** 2) / 2
    error, ns = ref.held_out_loss(h, v, t_ids, t_valid, c_ids, c_valid,
                                  ALPHA)
    assert ns == pytest.approx(want_ns, rel=1e-6)
    assert error == pytest.approx(want_err, rel=1e-6)
    # beyond the clip a pair adds exactly 0 when it is right and
    # 1e4 * alpha^2 when it is wrong: the error is bounded
    right = ref.held_out_loss(h * 100, v, t_ids, t_valid, c_ids, c_valid,
                              ALPHA)[0]
    wrong = ref.held_out_loss(-h * 100, v, t_ids, t_valid, c_ids, c_valid,
                              ALPHA)[0]
    assert right == 0.0 and wrong == pytest.approx(1e4 * ALPHA ** 2)
