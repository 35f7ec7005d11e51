"""The skip-gram configuration's pieces: its cell end to end at toy size, its
cost model against a hand count, its plain reference against the per-pair
loop of word2vec.c written out in Python floats, and its pair layout."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.costs import w2v_sg as costs
from benchmark.families import w2v_sg as family
from benchmark.lib import device, spec
from benchmark.reference import w2v_sg as ref

RUN = [sys.executable, os.path.join(spec.BENCH_DIR, "run.py")]
ALPHA, LR = 0.025, 0.7
ROWS, D, P, K = 20, 4, 12, 3


# -- the cell ------------------------------------------------------------------

@pytest.mark.parametrize("trace", ["0", "1"])
def test_sg2m_b2k_cpu_rehearsal_end_to_end(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(RUN + ["--workload", "sg2m-b2k", "--seed", "7",
                              "--seconds", "1", "--trace", trace,
                              "--rehearse-cpu"], capture_output=True,
                       text=True, timeout=240, env=env, cwd=spec.ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert "platform: cpu" in lines[0] and "config w2v-sg-2m-300" in lines[0]
    result = json.loads(lines[-1])
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "compared"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    # no time, rate or device metric in a rehearsal's result line
    assert set(result["metrics"]) <= {"train_loss_fixed"}
    assert "first step vs plain reference: ok" in p.stdout
    assert "programs lowered inside: 0" in p.stdout
    if trace == "1":
        readings = next(ln for ln in lines if "rehearsal readings" in ln)
        assert "step.pairs_per_step" in readings
        assert "step.pair_fill_share" in readings


def test_the_new_entries_resolve_and_are_reported_everywhere():
    assert spec.check() == []
    bench = spec.load_benchmark()
    cell = spec.load_cell("sg2m-b2k")
    assert cell.family == "w2v_sg" and cell.chips == 1
    w = cell.config["word2vec"]
    assert (w["len_vec"], w["window"], w["negative"], w["sg"],
            w["sample"]) == (300, 5, 5, 1, 1e-4)
    assert set(w) == {"len_vec", "window", "negative", "sg", "sample",
                      "learning_rate"}
    assert cell.config["reduced"] == ["vocab_size"]
    assert cell.config["published"]["vocab_size"] == 2_519_370
    assert cell.traffic["centers_per_step"] == 2048
    assert {m["name"] for m in cell.end_to_end} == {
        "words_per_s", "train_loss_fixed", "peak_hbm_gb", "setup_s"}
    for name in ("step.pairs_per_step", "step.pair_fill_share"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert "workloads" not in m               # every cell
        assert m["source"] == "program_counter"
    reported = {m["name"] for m, _ in cell.per_layer}
    assert {"w2v_step_roofline", "step.sample_ms_per_step",
            "step.pairs_per_step"} <= reported


def test_the_conf_holds_only_the_configurations_keys(tmp_path):
    cell = spec.load_cell("sg2m-b2k")
    fam = family.Family(cell.config, cell.traffic, 0, str(tmp_path), False,
                        None)
    assert fam.centers == 2048 and fam.minibatch == 20480
    conf = open(fam.write_conf()).read().split()
    assert "sg:" in conf and conf[conf.index("sg:") + 1] == "1"
    keys = {w for w in conf if w.endswith(":")}
    assert keys == {"len_vec:", "window:", "negative:", "sg:", "sample:",
                    "learning_rate:", "initial_learning_rate:", "minibatch:"}


# -- costs ---------------------------------------------------------------------

def test_rows_bytes_and_flops_hand_count():
    # 2 centers, window 1, 3 negatives, 5-wide f32 rows: 4 pairs, each
    # 1 input row + 4 target rows = 20 rows pulled and as many pushed
    assert costs.rows_per_step(2, 1, 3) == {"pairs": 4, "pulled": 20,
                                            "pushed": 20}
    # pulled 20 x 20 B; pushed 20 x (field 40 B + accumulator 40 B)
    assert costs.step_bytes(2, 1, 3, 5) == 400 + 1600
    # 16 (pair, target) terms x 5: f 160, v gradient 160, h gradient 80;
    # AdaGrad 5 x 20 rows x 5
    assert costs.step_flops(2, 1, 3, 5) == 160 + 160 + 80 + 500


def test_a_row_costs_what_the_cbow_cost_file_charges():
    # 1 center, window 1: skip-gram at K = 1 and CBOW at K = 3 both request
    # 6 rows, so both files must count the same bytes and the same floor's
    # byte side, at any width and item size
    from benchmark.costs import w2v as cbow
    assert costs.rows_per_step(1, 1, 1)["pulled"] \
        == cbow.rows_per_step(1, 1, 3)["pulled"] == 6
    for len_vec, itemsize in ((5, 4), (300, 4), (300, 2)):
        assert costs.step_bytes(1, 1, 1, len_vec, itemsize) \
            == cbow.step_bytes(1, 1, 3, len_vec, itemsize)


def test_b2k_is_the_143k_rows_of_the_issue():
    rows = costs.rows_per_step(2048, 5, 5)
    assert rows["pairs"] == 20_480 and rows["pulled"] == 143_360
    assert costs.step_bytes(2048, 5, 5, 300) == 143_360 * 1200 * 5
    floor = costs.step_floor_seconds(
        {"centers": 2048, "window": 5, "negative": 5, "len_vec": 300,
         "chips": 1}, device.peaks_for("TPU v5 lite"))
    assert floor["bound"] == "memory"
    assert floor["seconds"] == pytest.approx(860.16e6 / 819e9)
    # 70 row requests a center against CBOW's 21 at its own K
    assert rows["pulled"] / 2048 == 70


# -- the reference ---------------------------------------------------------------

def example():
    rng = np.random.default_rng(1)
    rows = {"h": rng.normal(0, 1.0, (ROWS, D)).astype(np.float32),
            "v": rng.normal(0, 1.0, (ROWS, D)).astype(np.float32),
            "h2sum": rng.random((ROWS, D)).astype(np.float32) * 0.01,
            "v2sum": rng.random((ROWS, D)).astype(np.float32) * 0.01}
    rows["h"][3] *= 8.0                    # |f| > 6: the clip must show
    t_ids = rng.integers(0, ROWS, (P, K + 1))
    c_ids = rng.integers(0, ROWS, P)
    t_valid = np.ones((P, K + 1), bool)
    c_valid = rng.random(P) < 0.8
    t_ids[:4, 0] = 3                       # one center, four pairs: its h
    c_valid[:4] = True                     # row is pushed four times
    t_ids[0, 2] = 3
    t_valid[0, 2] = False                  # a negative equal to the center
    c_ids[4:7] = 7                         # one input row, three pairs
    c_valid[4:7] = True
    c_valid[8] = False                     # a dead pair
    return rows, t_ids, t_valid, c_ids, c_valid


def by_hand(rows, t_ids, t_valid, c_ids, c_valid):
    h, v = rows["h"].astype(np.float64), rows["v"].astype(np.float64)
    grads = {"h": np.zeros((ROWS, D)), "v": np.zeros((ROWS, D))}
    count = {"h": np.zeros(ROWS), "v": np.zeros(ROWS)}
    clipped, err, terms, ns = 0, 0.0, 0, 0.0
    for p in range(P):
        if not c_valid[p]:
            continue
        c = c_ids[p]
        for k in range(K + 1):
            if not t_valid[p, k]:
                continue
            t, label = t_ids[p, k], 1.0 if k == 0 else 0.0
            f = float(v[c] @ h[t])
            if f > 6:
                g, clipped = (label - 1.0) * ALPHA, clipped + 1
            elif f < -6:
                g, clipped = (label - 0.0) * ALPHA, clipped + 1
            else:
                g = (label - 1.0 / (1.0 + math.exp(-f))) * ALPHA
            grads["h"][t] += g * v[c]
            count["h"][t] += 1
            grads["v"][c] += g * h[t]
            err, terms = err + 1e4 * g * g, terms + 1
            ns += math.log1p(math.exp(-f if label else f))
        count["v"][c] += 1
    out = {}
    for f, acc in (("h", "h2sum"), ("v", "v2sum")):
        g = grads[f] / np.maximum(count[f], 1)[:, None]
        a = rows[acc] + g * g
        out[acc] = a
        out[f] = rows[f] + LR * g / np.sqrt(a + 1e-6)
    return out, count, clipped, err / terms, ns / c_valid.sum()


def test_step_and_loss_match_the_per_pair_loop():
    rows, t_ids, t_valid, c_ids, c_valid = example()
    want, count, clipped, error, ns = by_hand(rows, t_ids, t_valid, c_ids,
                                              c_valid)
    assert clipped >= 1 and count["v"][7] == 3 and count["h"][3] >= 4
    got = ref.step(rows, t_ids, t_valid, c_ids, c_valid, ALPHA, LR)
    for f in want:
        assert got[f].dtype == np.float32
        np.testing.assert_allclose(got[f], want[f], rtol=2e-5, atol=1e-7)
        untouched = count[f[0]] == 0
        assert untouched.any()
        assert np.array_equal(got[f][untouched], rows[f][untouched])
    got_error, got_ns = ref.held_out_loss(rows["h"], rows["v"], t_ids,
                                          t_valid, c_ids, c_valid, ALPHA)
    assert got_error == pytest.approx(error, rel=1e-5)
    assert got_ns == pytest.approx(ns, rel=1e-5)


def test_tolerance_catches_bf16_rows_and_a_dropped_duplicate():
    import jax.numpy as jnp

    rows, t_ids, t_valid, c_ids, c_valid = example()
    want = ref.step(rows, t_ids, t_valid, c_ids, c_valid, ALPHA, LR)
    same = ref.compare(want, want, rows)
    assert all(f["ok"] and f["max_err"] == 0 for f in same.values())
    # the nearest precision below the configuration's float32
    bf16 = {f: np.asarray(jnp.asarray(a).astype(jnp.bfloat16)
                          .astype(jnp.float32)) for f, a in want.items()}
    assert not ref.compare(bf16, want, rows)["v"]["ok"]
    assert not ref.compare(bf16, want, rows)["h"]["ok"]
    dropped = c_valid.copy()
    dropped[1] = False                     # one of center 3's four pushes
    got = ref.step(rows, t_ids, t_valid, c_ids, dropped, ALPHA, LR)
    assert not ref.compare(got, want, rows)["h"]["ok"]


def test_an_untrained_table_reads_what_the_band_file_says():
    # h = 0: every f is 0, every g is +-0.5 alpha
    h, v = np.zeros((4, D), np.float32), np.ones((4, D), np.float32)
    t_ids, c_ids = np.zeros((3, K + 1), np.int32), np.arange(3)
    error, ns = ref.held_out_loss(h, v, t_ids, np.ones((3, K + 1), bool),
                                  c_ids, np.ones(3, bool), ALPHA)
    assert error == pytest.approx(1e4 * (0.5 * ALPHA) ** 2)   # 1.5625
    assert ns == pytest.approx((K + 1) * math.log(2.0), rel=1e-6)


# -- the pair layout ----------------------------------------------------------------

def test_pair_layout_of_a_grid():
    fam = family.Family.__new__(family.Family)
    fam.slot_of = np.arange(100, 200)            # word i lives in slot 100+i
    fam.free_slots = np.array([999], np.int32)
    centers = np.array([4, 9], np.int32)
    contexts = np.array([[1, 2], [3, 0]], np.int32)
    mask = np.array([[True, True], [True, False]])
    negs = np.array([[[4, 5], [6, 7]], [[8, 9], [1, 1]]], np.int32)
    t_rows, t_ids, t_valid, c_rows, c_ids, c_valid = fam._pair_layout(
        centers, contexts, mask, negs)
    assert t_ids.shape == t_valid.shape == (4, 3) and c_ids.shape == (4,)
    assert t_rows[t_ids].tolist() == [[104, 104, 105], [104, 106, 107],
                                      [109, 108, 109], [109, 101, 101]]
    # a negative equal to its center is skipped; a dead pair is all dead
    assert t_valid.tolist() == [[True, False, True], [True, True, True],
                                [True, True, False], [False] * 3]
    assert c_rows[c_ids].tolist() == [101, 102, 103, 100]
    assert c_valid.tolist() == [True, True, True, False]
    assert len(t_rows) == len(c_rows) == family.ROW_SAMPLE   # one bucket
