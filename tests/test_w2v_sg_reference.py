"""The skip-gram train step against the benchmark's plain reference.

One full step through ``Word2Vec._build_step()`` under ``sg: 1`` — the
per-pair draw, both pulls, the pair math, the sorted push with its per-key
mean and server-side AdaGrad — held to ``benchmark/reference/w2v_sg.py`` on
every row it touches within ``RTOL``, with every other row bit-identical.
Small (V 200, d 16, window 3, K 4, 32 centers) and on the CPU; all four
fields start seeded random and non-zero, so that no term vanishes.  The
benchmark makes the same comparison at 300 wide on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import w2v_sg as reference
from swiftmpi_tpu.cluster.cluster import Cluster
from swiftmpi_tpu.data.text import Vocab
from swiftmpi_tpu.models.word2vec import Word2Vec, _Tally
from swiftmpi_tpu.ops.sampling import sample_alias
from swiftmpi_tpu.utils import ConfigParser

V, D, WINDOW, K, B = 200, 16, 3, 4, 32
ALPHA, LR = 0.025, 0.7


def build(n_devices):
    cfg = ConfigParser().update({
        "cluster": {"transfer": "xla"},
        "word2vec": {"len_vec": D, "window": WINDOW, "negative": K, "sg": 1,
                     "sample": -1, "learning_rate": ALPHA},
        "server": {"initial_learning_rate": LR},
        "worker": {"minibatch": B * 2 * WINDOW},
    })
    cluster = Cluster(cfg, devices=jax.devices()[:n_devices]).initialize()
    model = Word2Vec(config=cfg, cluster=cluster, seed=3)
    rng = np.random.default_rng(17)
    keys = rng.permutation(10 * V)[:V].astype(np.uint64) + np.uint64(1)
    counts = np.sort(rng.integers(1, 500, V))[::-1].astype(np.int64)
    model.build_from_vocab(Vocab(keys, counts,
                                 dict(zip(keys.tolist(), range(V)))))
    table = model.table
    shape = (table.capacity, D)
    host = {"h": rng.normal(0, 0.5, shape), "v": rng.normal(0, 0.5, shape),
            "h2sum": rng.random(shape) * 0.05 + 1e-3,
            "v2sum": rng.random(shape) * 0.05 + 1e-3}
    host = {f: a.astype(np.float32) for f, a in host.items()}
    table.state = {f: jax.device_put(a, table.row_sharding())
                   for f, a in host.items()}
    return model, host, rng


def batch_for(case, model, rng, key):
    """(centers, contexts, mask) and the negatives ``key`` will draw (they
    depend on the key and the shape alone, so the batch can be bent
    towards them)."""
    W2 = 2 * WINDOW
    centers = rng.integers(0, V, B).astype(np.int32)
    contexts = rng.integers(0, V, (B, W2)).astype(np.int32)
    mask = rng.random((B, W2)) < 0.6
    mask[:, 0] = True                       # every center has a pair ...
    negs = np.asarray(sample_alias(key, model._alias_prob, model._alias_idx,
                                   (B, W2, K)))
    # no accidental edge cases in the base batch
    for b in range(B):
        while (negs[b] == centers[b]).any():
            centers[b] = rng.integers(0, V)
    if case == "negative_equals_center":
        centers[5] = negs[5, 0, 2]          # pair (5, 0): target 3 skipped
    elif case == "center_without_pair":
        mask[7] = False                     # ... but this one
    elif case == "same_center_twice":
        centers[11] = centers[4]            # 2 x up to 2W pushes of one row
    contexts = np.where(mask, contexts, 0)
    return centers, contexts, mask, negs


@pytest.mark.parametrize("case", ["plain", "negative_equals_center",
                                  "center_without_pair",
                                  "same_center_twice"])
@pytest.mark.parametrize("n_devices", [1, 8], ids=["one_device", "mesh8"])
def test_sg_step_matches_plain_reference(n_devices, case):
    if len(jax.devices()) < n_devices:
        pytest.skip(f"needs {n_devices} virtual devices")
    model, before, rng = build(n_devices)
    key = jax.random.key(2008)
    # the step is given the model's key and draws with what it splits off
    centers, contexts, mask, negs = batch_for(
        case, model, rng, jax.random.split(key)[1])
    step = model._build_step()
    assert model.resolved_rendering == "sg"
    state, _next_key, tally, err_sum = step(
        model.table.state, model._slot_of_vocab, model._alias_prob,
        model._alias_idx, jnp.asarray(centers), jnp.asarray(contexts),
        jnp.asarray(mask), key)
    err_cnt = _Tally.read(tally)["pair_count"]
    after = {f: np.asarray(a) for f, a in state.items()}

    # the pair layout of reference/w2v_sg.py, over the whole (small) table
    slot_of = np.asarray(model._slot_of_vocab)
    P = B * 2 * WINDOW
    t_words = np.concatenate(
        [np.broadcast_to(centers[:, None, None], negs.shape[:2] + (1,)),
         negs], axis=2).reshape(P, K + 1)
    c_valid = mask.reshape(P)
    t_valid = np.concatenate(
        [np.ones(negs.shape[:2] + (1,), bool),
         negs != centers[:, None, None]], axis=2).reshape(P, K + 1)
    t_valid &= c_valid[:, None]
    t_ids, c_ids = slot_of[t_words], slot_of[contexts.reshape(P)]
    if case == "negative_equals_center":
        assert not t_valid[5 * 2 * WINDOW, 3] and t_valid[5 * 2 * WINDOW, 0]
    if case == "center_without_pair":
        assert not t_valid[7 * 2 * WINDOW:8 * 2 * WINDOW].any()
    assert int(err_cnt) == t_valid.sum()

    want = reference.step(before, t_ids, t_valid, c_ids, c_valid, ALPHA, LR)
    touched = {"h": np.unique(t_ids[t_valid]), "v": np.unique(c_ids[c_valid])}
    for f in ("h", "h2sum", "v", "v2sum"):
        rows = touched[f[0]]
        rest = np.setdiff1d(np.arange(len(before[f])), rows)
        assert len(rows) and len(rest)
        assert np.array_equal(after[f][rest], before[f][rest]), f
        # every touched row moved (no term vanishes at these inputs)
        assert (after[f][rows] != before[f][rows]).any(axis=1).all(), f
    got = reference.compare({f: after[f][touched[f[0]]] for f in want},
                            {f: want[f][touched[f[0]]] for f in want},
                            {f: before[f][touched[f[0]]] for f in want})
    assert all(r["ok"] for r in got.values()), got
    if case == "same_center_twice":
        # the doubled center's h row took both centers' pairs as one key
        n = int((t_valid[:, 0] & (t_words[:, 0] == centers[4])).sum())
        assert n == mask[4].sum() + mask[11].sum()
    error, _ns = reference.held_out_loss(
        before["h"], before["v"], t_ids, t_valid, c_ids, c_valid, ALPHA)
    assert float(err_sum) / int(err_cnt) == pytest.approx(error, rel=1e-4)
