"""Window-coalesced push parity suite (ISSUE 4 acceptance).

The contract under test, per ``Transfer.push_window``:

* ``W == 1`` is the flatten of a unit axis — bit-identical to the
  per-step ``push`` on every backend.
* ``W > 1`` must equal the sum-then-apply-once oracle (flatten the
  window, one ``push``/``push_span``): every (step, position)
  contribution summed, mean over the TOTAL window contribution count,
  access rule once per unique row.  The dense wire format re-associates
  float sums, hence the looser rtol there.
* The sparse/dense wire-format crossover (``window_wire_format``) is
  host-static, steerable by ``window_expected_unique``, and visible in
  the traffic ledger (``window_sparse``/``window_dense``).
* Overflow accounting and the wire counters survive coalescing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from swiftmpi_tpu.cluster import SHARD_AXIS, ps_mesh
from swiftmpi_tpu.cluster.hashfrag import expected_unique_rows
from swiftmpi_tpu.parameter import KeyIndex, SparseTable, w2v_access
from swiftmpi_tpu.parameter.access import lr_access
from swiftmpi_tpu.parameter.key_index import (HotColdPartition,
                                              window_wire_format)
from swiftmpi_tpu.parameter.sparse_table import ef_name, hot_name
from swiftmpi_tpu.transfer.api import (ef_quantize_window,
                                       quant_grad_row_bytes,
                                       quantize_dequantize)
from swiftmpi_tpu.transfer.hybrid import HybridTransfer
from swiftmpi_tpu.transfer.local import LocalTransfer
from swiftmpi_tpu.transfer.tpu import TpuTransfer
from swiftmpi_tpu.transfer.xla import XlaTransfer
from swiftmpi_tpu.utils import ConfigParser

DIM = 8


def make_table(mesh=None, num_shards=8, cap=128, seed=0):
    access = w2v_access(learning_rate=0.3, len_vec=DIM)
    ki = KeyIndex(num_shards, cap)
    table = SparseTable(access, ki, mesh=mesh,
                        axis=SHARD_AXIS if mesh else None, seed=seed)
    return table, ki, access


def window_batch(ki, rng, W=4, B=64, key_hi=700):
    """A (W, B) window with padding (-1), duplicates across steps and
    within a step, plus integer counts — the full wire surface."""
    keys = rng.integers(0, key_hi, size=W * B).astype(np.uint64)
    slots = np.asarray(ki.lookup(keys), np.int32).reshape(W, B)
    slots[:, ::7] = -1
    grads = {f: rng.normal(size=(W, B, DIM)).astype(np.float32)
             for f in ("h", "v")}
    counts = rng.integers(1, 4, size=(W, B)).astype(np.float32)
    counts[slots < 0] = 0
    return slots, grads, counts


def oracle_window(state_np, slots, grads, access, mean=False, counts=None):
    """Sum-then-apply-once oracle: flatten the window, one local push."""
    flat = slots.reshape(-1)
    fgrads = {f: g.reshape(-1, DIM) for f, g in grads.items()}
    st = {f: v.copy() for f, v in state_np.items()}
    if counts is not None:
        return LocalTransfer().push_span(st, flat, fgrads,
                                         counts.reshape(-1), access,
                                         mean=mean)
    return LocalTransfer().push(st, flat, fgrads, access, mean=mean)


def backend(name, mesh):
    if name == "local":
        return LocalTransfer()
    if name == "xla":
        return XlaTransfer()
    if name == "tpu":
        return TpuTransfer(mesh)
    return HybridTransfer(mesh)


# -- W == 1: bit-identity on every backend --------------------------------

@pytest.mark.parametrize("name", ["local", "xla", "tpu", "hybrid"])
def test_push_window_w1_bit_identical(name, devices8):
    mesh = ps_mesh()
    table, ki, access = make_table(mesh)
    rng = np.random.default_rng(0)
    slots, grads, _ = window_batch(ki, rng, W=1, B=64)
    t = backend(name, mesh)
    state = table.state if name in ("tpu", "hybrid") else {
        f: jnp.asarray(np.asarray(v)) for f, v in table.state.items()}
    per_step = t.push(state, slots[0], {f: g[0] for f, g in grads.items()},
                      access, mean=True)
    win = t.push_window(state, slots, grads, access, mean=True)
    for f in access.fields:
        assert np.array_equal(np.asarray(per_step[f]), np.asarray(win[f])), \
            (name, f)


# -- W > 1: oracle parity through the sparse wire format ------------------

@pytest.mark.parametrize("mean,use_counts", [(False, False), (True, False),
                                             (True, True), (False, True)])
def test_tpu_push_window_matches_flat_oracle(mean, use_counts, devices8):
    mesh = ps_mesh()
    table, ki, access = make_table(mesh)
    state_np = {f: np.asarray(v) for f, v in table.state.items()}
    rng = np.random.default_rng(1)
    slots, grads, counts = window_batch(ki, rng)
    want = oracle_window(state_np, slots, grads, access, mean=mean,
                         counts=counts if use_counts else None)
    t = TpuTransfer(mesh)
    t.count_traffic = True
    got = t.push_window(table.state, slots, grads, access, mean=mean,
                        counts=counts if use_counts else None)
    for f in access.fields:
        np.testing.assert_allclose(np.asarray(got[f]), want[f], rtol=1e-5,
                                   atol=1e-6, err_msg=(f, mean, use_counts))
    tr = t.traffic()
    # one window, sparse format: dedup recorded rows in >= rows out, the
    # decision is visible, and the exchange hit the wire ledger
    assert tr["window_sparse"] == 1 and tr["window_dense"] == 0, tr
    assert tr["coalesced_rows_in"] >= tr["coalesced_rows_out"] > 0, tr
    assert tr["wire_bytes"] > 0 and tr["dispatches"] >= 1, tr


# -- sparse/dense crossover -----------------------------------------------

def test_window_wire_format_goldens_zipf_vs_uniform():
    """The host-static decision on two frequency shapes at identical
    geometry: a Zipf window dedups far below capacity (sparse pays), a
    uniform window's unique rows approach min(rows, vocab) (densify)."""
    vocab, rows, row_bytes = 50_000, 4 * 16_384, 68
    capacity = 65_536
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    zipf = np.maximum((1e6 * ranks ** -1.0 / np.sum(ranks ** -1.0))
                      .astype(np.int64), 1)
    uniform = np.full(vocab, 20, np.int64)
    eu_zipf = expected_unique_rows(zipf, rows)
    eu_uni = expected_unique_rows(uniform, rows)
    assert eu_zipf < eu_uni <= rows
    assert window_wire_format(rows, capacity, row_bytes,
                              expected_unique=eu_zipf) == "sparse"
    assert window_wire_format(rows, capacity, row_bytes,
                              expected_unique=eu_uni) == "dense"
    # no histogram hint: the raw request count decides
    assert window_wire_format(rows, capacity, row_bytes) == "dense"
    assert window_wire_format(8, capacity, row_bytes) == "sparse"


def test_tpu_push_window_dense_path_matches_oracle(devices8):
    """A window covering most of a tiny table crosses to the dense
    format: one capacity-shaped psum-style reduction, float-order noise
    only (hence the looser tolerance), decision counted as dense."""
    mesh = ps_mesh()
    table, ki, access = make_table(mesh, cap=8)
    state_np = {f: np.asarray(v) for f, v in table.state.items()}
    rng = np.random.default_rng(2)
    slots, grads, _ = window_batch(ki, rng, key_hi=24)
    want = oracle_window(state_np, slots, grads, access, mean=True)
    t = TpuTransfer(mesh)
    t.count_traffic = True
    got = t.push_window(table.state, slots, grads, access, mean=True)
    for f in access.fields:
        np.testing.assert_allclose(np.asarray(got[f]), want[f], rtol=1e-4,
                                   atol=1e-5, err_msg=f)
    tr = t.traffic()
    assert tr["window_dense"] == 1 and tr["window_sparse"] == 0, tr
    # dense wire volume is the static table size, not the row count
    assert tr["wire_bytes"] >= ki.capacity * DIM * 4, tr


def test_window_expected_unique_steers_runtime_decision(devices8):
    """Same batch, same capacity: the raw request count alone densifies,
    but a Zipf-aware expected-unique hint below the crossover keeps the
    window sparse — and both results agree with the oracle."""
    mesh = ps_mesh()
    table, ki, access = make_table(mesh, cap=8)     # capacity 64
    state_np = {f: np.asarray(v) for f, v in table.state.items()}
    rng = np.random.default_rng(3)
    slots, grads, _ = window_batch(ki, rng, key_hi=16)
    want = oracle_window(state_np, slots, grads, access, mean=True)

    dense_t = TpuTransfer(mesh)
    dense_t.count_traffic = True
    assert dense_t.window_expected_unique is None
    got_d = dense_t.push_window(table.state, slots, grads, access,
                                mean=True)
    assert dense_t.traffic()["window_dense"] == 1

    sparse_t = TpuTransfer(mesh)
    sparse_t.count_traffic = True
    sparse_t.window_expected_unique = 16.0
    got_s = sparse_t.push_window(table.state, slots, grads, access,
                                 mean=True)
    tr = sparse_t.traffic()
    assert tr["window_sparse"] == 1 and tr["window_dense"] == 0, tr
    for f in access.fields:
        np.testing.assert_allclose(np.asarray(got_d[f]), want[f],
                                   rtol=1e-4, atol=1e-5, err_msg=f)
        np.testing.assert_allclose(np.asarray(got_s[f]), want[f],
                                   rtol=1e-4, atol=1e-5, err_msg=f)


# -- hybrid hot/tail split ------------------------------------------------

def test_hybrid_push_window_hot_split_parity(devices8):
    """n_hot > 0: the window dedups once in the unified slot space, the
    hot slice reconciles via the dense psum, the tail slice rides the
    tpu window path — against the unified flatten-once oracle.  The
    wire decision of the tail slice must be visible in the ledger."""
    mesh = ps_mesh()
    rng = np.random.default_rng(4)
    keys = rng.choice(100_000, size=400, replace=False).astype(np.uint64)
    ranks = np.arange(1, 401, dtype=np.float64)
    counts = np.maximum((1e6 * ranks ** -1.0 / np.sum(ranks ** -1.0))
                        .astype(np.int64), 1)[rng.permutation(400)]
    part = HotColdPartition.from_counts(keys, counts, batch_rows=64)
    access = w2v_access(learning_rate=0.3, len_vec=DIM)
    ki = KeyIndex(8, 64, partition=part)
    table = SparseTable(access, ki, mesh=mesh, axis=SHARD_AXIS)
    ki.lookup(keys)
    assert table.n_hot > 0

    W, B = 3, 64
    slots = np.asarray(ki.lookup(keys[rng.integers(0, 400, W * B)]),
                       np.int32).reshape(W, B)
    slots[:, ::9] = -1
    assert ((slots >= 0) & (slots < table.n_hot)).any()
    assert (slots >= table.n_hot).any()
    grads = {f: rng.normal(size=(W, B, DIM)).astype(np.float32)
             for f in ("h", "v")}
    uni_state = {f: table.unified_rows_host(f) for f in access.fields}
    want = oracle_window(uni_state, slots, grads, access, mean=True)

    t = HybridTransfer(mesh)
    t.count_traffic = True
    new = t.push_window(table.state, slots, grads, access, mean=True)
    for f in access.fields:
        got_uni = np.concatenate([np.asarray(new[hot_name(f)]),
                                  np.asarray(new[f])])
        np.testing.assert_allclose(got_uni, want[f], rtol=1e-5, atol=1e-6,
                                   err_msg=f)
    tr = t.traffic()
    assert tr["window_sparse"] + tr["window_dense"] == 1, tr
    assert tr["coalesced_rows_in"] >= tr["coalesced_rows_out"] > 0, tr
    assert tr["hot_rows"] > 0 and tr["psum_bytes"] > 0, tr


# -- overflow accounting --------------------------------------------------

def test_push_window_overflow_preserved(devices8):
    """Bucket overflow through the coalesced sparse path counts exactly
    like the per-step push of the same flattened rows (dedup leaves the
    all-distinct batch untouched, so the routed load is identical)."""
    mesh = ps_mesh()
    access = lr_access(0.1)
    ki = KeyIndex(num_shards=8, capacity_per_shard=64)
    table = SparseTable(access, ki, mesh=mesh, axis=SHARD_AXIS)
    keys, k = [], 0
    while len(keys) < 24:       # all owned by shard 3 -> tiny buckets drop
        if ki.shard_of(np.array([k], np.uint64))[0] == 3:
            keys.append(k)
        k += 1
    flat = np.asarray(ki.lookup(np.array(keys, np.uint64)), np.int32)
    grads_flat = {"val": np.ones((24, 1), np.float32)}

    ref = TpuTransfer(mesh, bucket_capacity=2)
    ref.push(table.state, flat, grads_flat, access)
    want_dropped = ref.overflow_count()
    assert want_dropped > 0

    t = TpuTransfer(mesh, bucket_capacity=2)
    t.count_traffic = True
    t.push_window(table.state, flat.reshape(2, 12),
                  {"val": grads_flat["val"].reshape(2, 12, 1)}, access)
    assert t.overflow_count() == want_dropped
    assert t.traffic()["window_sparse"] == 1

    ample = TpuTransfer(mesh, bucket_capacity=24)
    ample.push_window(table.state, flat.reshape(2, 12),
                      {"val": grads_flat["val"].reshape(2, 12, 1)}, access)
    assert ample.overflow_count() == 0


# -- wire counters exist on every backend ---------------------------------

@pytest.mark.parametrize("name", ["local", "xla", "tpu", "hybrid"])
def test_traffic_counters_all_backends(name, devices8):
    mesh = ps_mesh()
    table, ki, access = make_table(mesh)
    rng = np.random.default_rng(5)
    slots, grads, _ = window_batch(ki, rng, W=2, B=64)
    t = backend(name, mesh)
    t.count_traffic = True
    state = table.state if name in ("tpu", "hybrid") else {
        f: jnp.asarray(np.asarray(v)) for f, v in table.state.items()}
    t.push_window(state, slots, grads, access, mean=True)
    tr = t.traffic()
    for key in ("wire_bytes", "dispatches", "window_sparse",
                "window_dense", "coalesced_rows_in", "coalesced_rows_out"):
        assert key in tr, (name, tr)
    assert tr["wire_bytes"] > 0 and tr["dispatches"] >= 1, (name, tr)


# -- windowed AdaGrad envelope --------------------------------------------

def test_windowed_adagrad_accumulator_envelope():
    """The documented bounded-staleness envelope (sparse_table.py
    docstring): one window advances the accumulator by (Σg)² instead of
    Σ(g²) per step — within [0, W x per-step mass] by Cauchy-Schwarz,
    reaching W x when the window's gradients align and 0 when they
    cancel."""
    access = w2v_access(learning_rate=0.3, len_vec=DIM)
    W = 4
    for case, scale in [("aligned", np.ones(W)),
                        ("cancel", np.array([1.0, -1.0, 1.0, -1.0])),
                        ("mixed", np.array([0.5, -0.2, 1.0, 0.3]))]:
        g = np.stack([s * np.ones((1, DIM), np.float32) for s in scale])
        slots = np.zeros((W, 1), np.int32)
        zero = {f: np.zeros((4, DIM), np.float32)
                for f in ("h", "v", "h2sum", "v2sum")}
        win = LocalTransfer().push_window(
            {f: v.copy() for f, v in zero.items()}, slots,
            {"h": g}, access)
        win_mass = float(np.asarray(win["h2sum"])[0].sum())
        st = {f: v.copy() for f, v in zero.items()}
        for i in range(W):
            st = LocalTransfer().push(st, slots[i], {"h": g[i]}, access)
        step_mass = float(np.asarray(st["h2sum"])[0].sum())
        np.testing.assert_allclose(win_mass, float((g.sum(0) ** 2).sum()),
                                   rtol=1e-6)
        assert 0.0 <= win_mass <= W * step_mass + 1e-6, (case, win_mass,
                                                         step_mass)
        if case == "aligned":
            np.testing.assert_allclose(win_mass, W * step_mass, rtol=1e-6)
        if case == "cancel":
            assert win_mass < 1e-6


# -- word2vec end-to-end --------------------------------------------------

def w2v_model(**overrides):
    from swiftmpi_tpu.models.word2vec import Word2Vec

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


@pytest.mark.slow
def test_w2v_push_window_training_parity(devices8):
    """push_window=2 over the fused scan trains to the same loss
    trajectory as the per-step path (within the bounded-staleness band —
    the same 25% envelope the async/staleness suites use).

    Slow lane (~7s: two full e2e trains): tier-1 keeps the sharper
    transfer-level window oracles above (coalesced window == sum of
    per-step pushes, bit-exact) and the dense-logits guard below."""
    from swiftmpi_tpu.data.text import synthetic_corpus

    corpus = synthetic_corpus(90, vocab_size=60, length=12, seed=8)
    base = w2v_model(worker={"inner_steps": 4})
    base_losses = base.train(corpus, niters=3, batch_size=64)
    win = w2v_model(cluster={"transfer": "xla", "push_window": 2},
                    worker={"inner_steps": 4})
    win_losses = win.train(corpus, niters=3, batch_size=64)
    assert win_losses[-1] < win_losses[0]
    for a, b in zip(win_losses, base_losses):
        assert abs(a - b) / b < 0.25, (win_losses, base_losses)


# -- 4-way wire compression (sparse_q / bitmap + error feedback) ----------

def distinct_window(ki, rng, W=2, B=64):
    """All-distinct keys (plus padding): the tpu backend's device-LOCAL
    dedup then equals the global dedup, so every quantized sum is
    quantized exactly once and the device paths are tightly comparable
    to the numpy oracle (no summation-order noise under the per-bucket
    int8 scales)."""
    keys = rng.choice(5000, size=W * B, replace=False).astype(np.uint64)
    slots = np.asarray(ki.lookup(keys), np.int32).reshape(W, B)
    slots[:, ::9] = -1
    grads = {f: rng.normal(size=(W, B, DIM)).astype(np.float32)
             for f in ("h", "v")}
    counts = rng.integers(1, 4, size=(W, B)).astype(np.float32)
    counts[slots < 0] = 0
    return slots, grads, counts


def test_window_wire_format_4way_goldens():
    """Byte-model goldens for the calibrated 4-way crossover.  The
    dense gate is the OLD 2-way rule checked first verbatim, so
    arming quantization can never move the sparse/dense boundary."""
    cap = 1024
    # quant="off" reproduces the 2-way decision bit-identically, with
    # or without a (stale) quantized-row estimate supplied
    for rows in (8, 100, 4 * 16_384):
        for eu in (None, 16.0, 400.0):
            want = window_wire_format(rows, cap, 68, expected_unique=eu)
            got = window_wire_format(rows, cap, 68, expected_unique=eu,
                                     quant="off", quant_row_bytes=40)
            assert got == want, (rows, eu)
    # a dense window stays dense no matter how cheap quantized rows look
    assert window_wire_format(4 * 16_384, cap, 68) == "dense"
    assert window_wire_format(4 * 16_384, cap, 68, quant="int8",
                              quant_row_bytes=1) == "dense"
    # d=8 two-field geometry (lossless row 72B, int8 row 32B): the
    # quantized volume beats both lossless encodings by more than the
    # 1.25x guard -> sparse_q; without the estimate the capacity/8
    # occupancy mask still beats per-row index words at this density
    assert window_wire_format(256, cap, 72, quant="int8",
                              quant_row_bytes=32) == "sparse_q"
    assert window_wire_format(256, cap, 72, quant="int8",
                              quant_row_bytes=None) == "bitmap"
    # a stricter guard demands a bigger win: fall back to lossless bitmap
    assert window_wire_format(256, cap, 72, quant="int8",
                              quant_row_bytes=32,
                              quant_guard=2.5) == "bitmap"
    # d=1 geometry: the 4-byte per-bucket scale word makes int8 rows
    # BIGGER than bitmap rows -> bitmap wins even with quant armed
    assert window_wire_format(256, cap, 12, quant="int8",
                              quant_row_bytes=13) == "bitmap"
    # low density: the mask amortizes over too few rows, and bf16's
    # 10-byte row cannot beat the 12-byte lossless row by the guard
    assert window_wire_format(8, cap, 12, quant="bf16",
                              quant_row_bytes=10) == "sparse"


def test_ef_quantize_window_duplicate_owner_identity():
    """tpu's window dedup is device-LOCAL: the same slot can survive as
    owner in several devices' batch slices.  The EF drain must stay
    exact anyway — the prior residual drains into the globally FIRST
    occurrence only, and the error write-back scatter-ADDs (commutes
    under duplicates)."""
    cap, d = 16, 4
    rng = np.random.default_rng(6)
    ef0 = (rng.normal(size=(cap, d)) * 0.01).astype(np.float32)
    state = {"h": jnp.zeros((cap, d), jnp.float32),
             "h@ef": jnp.asarray(ef0)}
    ded_slots = jnp.asarray(np.array([3, 3, -1, 5], np.int32))
    g = rng.normal(size=(4, d)).astype(np.float32)
    g[2] = 0.0
    out_state, out_grads = ef_quantize_window(
        state, ded_slots, {"h": jnp.asarray(g)}, cap, "int8")
    deq = np.asarray(out_grads["h"])
    ef1 = np.asarray(out_state["h@ef"])
    assert np.all(deq[2] == 0)                  # padding ships zeros
    # per-slot EF identity, duplicate owners and all:
    #   sum(applied deq) + residual' == sum(true grads) + residual
    for s, rows in ((3, [0, 1]), (5, [3])):
        np.testing.assert_allclose(
            deq[rows].sum(0) + ef1[s], g[rows].sum(0) + ef0[s],
            rtol=1e-5, atol=1e-6, err_msg=s)
    untouched = np.setdiff1d(np.arange(cap), [3, 5])
    assert np.array_equal(ef1[untouched], ef0[untouched])
    # the residual is quantization ERROR, not a copy: bounded by one
    # int8 step of each contributing row's bucket scale
    tot0 = g[0] + ef0[3]
    bound = (np.abs(tot0).max() + np.abs(g[1]).max()) / 127.0
    assert np.abs(ef1[3]).max() <= bound + 1e-7


def test_ef_drain_exactness_numpy_oracle():
    """Local sparse_q pipeline vs a from-scratch numpy simulation over
    three windows: the banked residual planes are bit-equal to the
    simulation, the routed grads are exactly the independently
    quantized sums, the wire ledger books the ENCODED size, and the EF
    telescope sum(applied) + residual_final == sum(true grads)
    closes."""
    table, ki, access = make_table()            # capacity 1024
    table.ensure_ef(("h", "v"))
    state = {f: np.asarray(v).copy() for f, v in table.state.items()}
    t = LocalTransfer()
    t.wire_quant = "int8"
    t.count_traffic = True
    rng = np.random.default_rng(7)
    cap = ki.capacity
    ef_sim = {f: np.zeros((cap, DIM), np.float32) for f in ("h", "v")}
    true_tot = {f: np.zeros((cap, DIM), np.float32) for f in ("h", "v")}
    applied = {f: np.zeros((cap, DIM), np.float32) for f in ("h", "v")}
    want_bytes = 0
    for _ in range(3):
        slots, grads, _ = window_batch(ki, rng, W=2, B=32)
        prev = {f: v.copy() for f, v in state.items()}
        state = {f: np.asarray(v) for f, v in t.push_window(
            state, slots, grads, access, mean=False).items()}
        # -- independent simulation of the same window ------------------
        flat = slots.reshape(-1)
        valid = flat >= 0
        uniq = np.unique(flat[valid])
        pos = np.searchsorted(uniq, flat[valid])
        deq_sums = {}
        for f in ("h", "v"):
            sums = np.zeros((len(uniq), DIM), np.float32)
            np.add.at(sums, pos, grads[f].reshape(-1, DIM)[valid])
            true_tot[f][uniq] += sums
            tot = sums + ef_sim[f][uniq]
            deq = np.asarray(quantize_dequantize(tot, "int8"),
                             np.float32)
            ef_sim[f][uniq] = tot - deq
            applied[f][uniq] += deq
            deq_sums[f] = deq
            # the pipeline banked exactly the simulated residual
            assert np.array_equal(state[ef_name(f)], ef_sim[f]), f
        # and the table update is exactly push_span of the simulated
        # dequantized sums at the deduped slots
        csum = np.zeros((len(uniq),), np.float32)
        np.add.at(csum, pos, np.ones(int(valid.sum()), np.float32))
        want = LocalTransfer().push_span(prev, uniq, deq_sums, csum,
                                         access, mean=False)
        for f in access.fields:
            assert np.array_equal(state[f], np.asarray(want[f])), f
        want_bytes += len(uniq) * quant_grad_row_bytes(
            deq_sums, "int8", with_counts=True)
    # residuals are live (quantization actually erred somewhere) and the
    # telescope closes: nothing was lost, nothing double-applied
    assert any(ef_sim[f].any() for f in ("h", "v"))
    for f in ("h", "v"):
        np.testing.assert_allclose(applied[f] + ef_sim[f], true_tot[f],
                                   rtol=1e-6, atol=1e-5, err_msg=f)
    tr = t.traffic()
    assert tr["window_fmt_q"] == 3 and tr["window_sparse"] == 3, tr
    assert tr["window_fmt_bitmap"] == 0 and tr["window_dense"] == 0, tr
    assert tr["wire_bytes"] == want_bytes, (tr, want_bytes)


@pytest.mark.parametrize("name", ["xla", "tpu", "hybrid"])
def test_sparse_q_window_matches_numpy_oracle(name, devices8):
    """Device sparse_q windows against the armed local oracle: same
    quantized values applied, same residuals banked, exchange booked at
    encoded size on every backend."""
    mesh = ps_mesh()
    table, ki, access = make_table(mesh)
    table.ensure_ef(("h", "v"))
    rng = np.random.default_rng(13)
    slots, grads, counts = distinct_window(ki, rng)
    state_np = {f: np.asarray(v).copy() for f, v in table.state.items()}
    lo = LocalTransfer()
    lo.wire_quant = "int8"
    want = lo.push_window({f: v.copy() for f, v in state_np.items()},
                          slots, grads, access, mean=True, counts=counts)
    t = backend(name, mesh)
    t.wire_quant = "int8"
    t.count_traffic = True
    state = table.state if name in ("tpu", "hybrid") else {
        f: jnp.asarray(v) for f, v in state_np.items()}
    got = t.push_window(state, slots, grads, access, mean=True,
                        counts=counts)
    for f in list(access.fields) + [ef_name("h"), ef_name("v")]:
        np.testing.assert_allclose(np.asarray(got[f]),
                                   np.asarray(want[f]), rtol=1e-5,
                                   atol=1e-6, err_msg=(name, f))
    tr = t.traffic()
    assert tr["window_fmt_q"] == 1 and tr["window_fmt_bitmap"] == 0, tr
    assert tr["window_sparse"] == 1 and tr["window_dense"] == 0, tr
    # booked at ENCODED size: unique rows x int8 row bytes — less than
    # half the lossless sparse volume at d=8 x 2 fields
    nvalid = int((slots >= 0).sum())
    qrb = quant_grad_row_bytes(
        {f: g.reshape(-1, DIM) for f, g in grads.items()}, "int8",
        with_counts=True)
    assert tr["wire_bytes"] == nvalid * qrb, (tr, nvalid, qrb)
    assert 2 * tr["wire_bytes"] < nvalid * (4 + 4 * 2 * DIM + 4)


def test_sparse_q_xla_duplicate_window_matches_oracle(devices8):
    """Duplicates across and within steps: xla's global representative
    dedup must agree with the numpy oracle — sums folded once, residual
    drained once, then quantized once."""
    table, ki, access = make_table()
    table.ensure_ef(("h", "v"))
    rng = np.random.default_rng(14)
    slots, grads, counts = window_batch(ki, rng, W=2, B=64)
    state_np = {f: np.asarray(v).copy() for f, v in table.state.items()}
    lo = LocalTransfer()
    lo.wire_quant = "int8"
    want = lo.push_window({f: v.copy() for f, v in state_np.items()},
                          slots, grads, access, mean=True, counts=counts)
    x = XlaTransfer()
    x.wire_quant = "int8"
    got = x.push_window({f: jnp.asarray(v) for f, v in state_np.items()},
                        slots, grads, access, mean=True, counts=counts)
    for f in list(access.fields) + [ef_name("h"), ef_name("v")]:
        np.testing.assert_allclose(np.asarray(got[f]),
                                   np.asarray(want[f]), rtol=1e-5,
                                   atol=1e-5, err_msg=f)


@pytest.mark.parametrize("name", ["local", "xla", "tpu"])
def test_bitmap_window_parity_and_byte_booking(name, devices8):
    """d=1 geometry: the 4-byte per-bucket scale word makes int8 rows
    BIGGER than bitmap rows, so the decision lands on bitmap — whose
    payload is the plain lossless sums (only the BOOKED wire
    representation changes: capacity/8 mask + packed rows, no index
    words)."""
    mesh = ps_mesh()
    access = lr_access(0.1)
    ki = KeyIndex(num_shards=8, capacity_per_shard=128)   # capacity 1024
    table = SparseTable(access, ki, mesh=mesh, axis=SHARD_AXIS)
    rng = np.random.default_rng(15)
    keys = rng.choice(4000, size=256, replace=False).astype(np.uint64)
    slots = np.asarray(ki.lookup(keys), np.int32).reshape(2, 128)
    grads = {"val": rng.normal(size=(2, 128, 1)).astype(np.float32)}
    state_np = {f: np.asarray(v).copy() for f, v in table.state.items()}
    want = LocalTransfer().push_window(
        {f: v.copy() for f, v in state_np.items()}, slots, grads,
        access, mean=True)
    t = backend(name, mesh)
    t.wire_quant = "int8"
    t.count_traffic = True
    state = table.state if name == "tpu" else {
        f: jnp.asarray(v) for f, v in state_np.items()}
    got = t.push_window(state, slots, grads, access, mean=True)
    for f in access.fields:
        np.testing.assert_allclose(np.asarray(got[f]),
                                   np.asarray(want[f]), rtol=1e-5,
                                   atol=1e-6, err_msg=(name, f))
    tr = t.traffic()
    assert tr["window_fmt_bitmap"] == 1 and tr["window_fmt_q"] == 0, tr
    assert tr["wire_bytes"] == 256 * 8 + 1024 // 8, tr


@pytest.mark.parametrize("name", ["local", "xla", "tpu", "hybrid"])
def test_wire_quant_off_bit_identity_all_backends(name, devices8):
    """``wire_quant: off`` must be STRUCTURALLY the pre-quantization
    path: bit-identical results even with @ef planes parked in the
    state, residuals untouched, no q/bitmap decisions booked."""
    mesh = ps_mesh()
    table, ki, access = make_table(mesh)
    rng = np.random.default_rng(16)
    slots, grads, _ = window_batch(ki, rng, W=2, B=64)
    plain = dict(table.state)               # snapshot WITHOUT EF planes
    table.ensure_ef(("h", "v"))
    armed = table.state                     # same arrays + @ef zeros

    def dev(st):
        return st if name in ("tpu", "hybrid") else {
            f: jnp.asarray(np.asarray(v)) for f, v in st.items()}

    base_t = backend(name, mesh)
    want = base_t.push_window(dev(plain), slots, grads, access,
                              mean=True)
    t = backend(name, mesh)
    t.wire_quant = "off"                    # the explicit escape hatch
    t.count_traffic = True
    got = t.push_window(dev(armed), slots, grads, access, mean=True)
    for f in access.fields:
        assert np.array_equal(np.asarray(got[f]), np.asarray(want[f])), \
            (name, f)
    for f in ("h", "v"):
        assert np.array_equal(np.asarray(got[ef_name(f)]),
                              np.asarray(armed[ef_name(f)])), (name, f)
    tr = t.traffic()
    assert tr["window_fmt_q"] == 0 and tr["window_fmt_bitmap"] == 0, tr
    if name in ("tpu", "hybrid"):
        # the decision-making backends book the 2-way split; the base
        # flatten path (local/xla off) never did and still must not
        assert tr["window_fmt_dense"] + tr["window_fmt_sparse"] == 1, tr
    else:
        assert tr["window_fmt_dense"] + tr["window_fmt_sparse"] == 0, tr


def test_window_fmt_telemetry_mirror():
    """Satellite: the 4-way decision counters mirror into the registry
    as ONE fmt-labeled series ``transfer/window_fmt{backend=, fmt=}``
    next to the legacy 2-way mirrors."""
    from swiftmpi_tpu import obs

    table, ki, access = make_table()
    table.ensure_ef(("h", "v"))
    state = {f: np.asarray(v).copy() for f, v in table.state.items()}
    obs.set_enabled(True)
    try:
        t = LocalTransfer()
        t.wire_quant = "int8"
        t.count_traffic = True
        rng = np.random.default_rng(17)
        slots, grads, _ = window_batch(ki, rng, W=2, B=32)
        t.push_window(state, slots, grads, access, mean=False)
        reg = obs.get_registry()
        assert reg.counter("transfer/window_fmt", backend="local",
                           fmt="q").value == 1
        assert reg.counter("transfer/window_sparse",
                           backend="local").value == 1
    finally:
        obs.set_enabled(False)


@pytest.mark.slow
def test_w2v_sparse_q_trajectory_parity(devices8):
    """[cluster] wire_quant: int8 through the fused windowed scan tracks
    the f32 wire within the documented envelope |a-b| <= 1e-5 + 1e-3|b|
    over a 3-epoch run, with the decision mix showing sparse_q engaged
    and every booked byte at the encoded (28B/row) size."""
    from swiftmpi_tpu.data.text import synthetic_corpus

    corpus = synthetic_corpus(160, vocab_size=300, length=12, seed=21)
    kw = dict(cluster={"transfer": "xla", "push_window": 2},
              worker={"inner_steps": 4, "minibatch": 64})
    base = w2v_model(**kw)
    base.transfer.count_traffic = True
    base_losses = base.train(corpus, niters=3, batch_size=64)
    qkw = dict(kw, cluster=dict(kw["cluster"], wire_quant="int8"))
    q = w2v_model(**qkw)
    q.transfer.count_traffic = True
    q_losses = q.train(corpus, niters=3, batch_size=64)
    assert q_losses[-1] < q_losses[0]
    for a, b in zip(q_losses, base_losses):
        assert abs(a - b) <= 1e-5 + 1e-3 * abs(b), (q_losses,
                                                    base_losses)
    tr_q, tr_b = q.transfer.traffic(), base.transfer.traffic()
    assert tr_q["window_fmt_q"] > 0, tr_q
    assert tr_b["window_fmt_q"] == 0 and tr_b["window_fmt_bitmap"] == 0
    # every window went sparse_q and was booked at ENCODED size: the
    # int8 row (4B index + 16+4B values/scale + 4B counts = 28B) against
    # the 72B lossless row — >2x fewer wire bytes for the same routed
    # rows.  (Cross-run wire_bytes totals are not comparable on xla: its
    # per-step dense push books eagerly per trace, a pre-existing
    # ledger quirk outside the window path.)
    rows_out = tr_q["coalesced_rows_out"]
    assert rows_out > 0 and tr_q["wire_bytes"] == rows_out * 28, tr_q
    assert 2 * tr_q["wire_bytes"] < rows_out * 72, tr_q


def test_checkpoint_roundtrip_carries_ef_planes(tmp_path, devices8):
    """Satellite: @ef residual planes ride the binary checkpoint both
    ways, and an EF arming mismatch between checkpoint and table is a
    LOUD error in either direction — silent drops of pending residual
    mass are exactly the failure the telescope identity forbids."""
    from swiftmpi_tpu.io.checkpoint import load_checkpoint, save_checkpoint

    table, ki, access = make_table()
    table.ensure_ef(("h", "v"))
    rng = np.random.default_rng(18)
    res = (rng.normal(size=(ki.capacity, DIM)) * 1e-3).astype(np.float32)
    state = dict(table.state)
    state[ef_name("h")] = jnp.asarray(res)
    table.state = state
    path = str(tmp_path / "ck")
    save_checkpoint(table, path, extra={"iter": np.int64(1)})

    back, _, _ = make_table(seed=1)
    back.ensure_ef(("h", "v"))
    load_checkpoint(back, path)
    np.testing.assert_array_equal(np.asarray(back.state[ef_name("h")]),
                                  res)
    assert not np.asarray(back.state[ef_name("v")]).any()

    # EF checkpoint into a quant-off table: pending residuals would
    # silently vanish -> refuse loudly
    plain, _, _ = make_table(seed=2)
    with pytest.raises(ValueError, match="wire_quant"):
        load_checkpoint(plain, path)
    # mirror image: non-EF checkpoint into an EF-armed table
    p2 = str(tmp_path / "ck2")
    save_checkpoint(make_table(seed=3)[0], p2)
    armed, _, _ = make_table(seed=4)
    armed.ensure_ef(("h",))
    with pytest.raises(ValueError, match="wire_quant"):
        load_checkpoint(armed, p2)


def test_chaos_resume_mid_window_preserves_ef(tmp_path, devices8):
    """Satellite chaos scenario: a crash mid-stream with wire_quant
    armed restarts from the checkpoint WITH its @ef planes (no silent
    zero-reseed) and trains on to finite losses."""
    from swiftmpi_tpu.data.text import CBOWBatcher, synthetic_corpus
    from swiftmpi_tpu.io.checkpoint import npz_path
    from swiftmpi_tpu.io.resilience import train_with_resume

    corpus = synthetic_corpus(60, vocab_size=200, length=12, seed=22)
    m = w2v_model(cluster={"transfer": "xla", "push_window": 2,
                           "wire_quant": "int8"},
                  worker={"inner_steps": 4, "minibatch": 64})
    m.build(corpus)
    assert sorted(m.table.ef_fields) == ["h@ef", "v@ef"]

    class Flaky:
        def __init__(self, inner):
            self.inner = inner
            self.epoch_i = 0

        def epoch(self, batch_size):
            self.epoch_i += 1
            for i, b in enumerate(self.inner.epoch(batch_size)):
                if self.epoch_i == 2 and i == 1:
                    raise RuntimeError("injected crash mid-stream")
                yield b

    flaky = Flaky(CBOWBatcher(corpus, m.vocab, m.window))
    ckpt = str(tmp_path / "qck")
    losses = train_with_resume(m, niters=3, checkpoint_path=ckpt,
                               checkpoint_every=1, max_restarts=2,
                               batcher=flaky, batch_size=64)
    # crash in epoch 2, checkpoint at iter 1 restored, 2 iters rerun
    assert len(losses) == 2 and np.isfinite(losses).all()
    with np.load(npz_path(ckpt)) as z:
        assert "field__h@ef" in z.files and "field__v@ef" in z.files
    assert sorted(m.table.ef_fields) == ["h@ef", "v@ef"]
