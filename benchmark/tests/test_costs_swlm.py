"""costs/swlm.py against a hand count of trinity-mini-ep16's step, the new
traffic mix's determinism, and BENCHMARK.json with its two four-chip cells
of nine against the driver's limits."""

import numpy as np
import pytest

from benchmark.costs import swlm as costs
from benchmark.lib import spec, traffic

KINDS = [("sliding", "dense"), ("sliding", "moe"), ("full", "moe"),
         ("sliding", "moe"), ("sliding", "moe")]
SHAPE = {"tokens": 16384, "seq_len": 16384, "kinds": KINDS, "d_model": 2048,
         "heads": 32, "kv_heads": 4, "d_head": 128, "d_ff": 6144,
         "d_expert": 1024, "d_shared": 1024, "experts": 128,
         "experts_held": 8, "top_k": 8, "vocab": 25024, "window": 2048,
         "attn_block": 512, "held_pick_share": 6.25,
         "parameters": 504_147_712, "chips": 1}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_pairs_and_tiles_by_hand():
    S, w = 16384, 2048
    # a query sees itself and the 2,047 before it; the first 2,048 fewer
    assert costs.visible_pairs(SHAPE, "sliding") == \
        w * (w + 1) // 2 + (S - w) * w == 31_458_304
    assert costs.visible_pairs(SHAPE, "full") == S * (S + 1) // 2 \
        == 134_225_920
    # 32 tiles of 512: query tile i >= 4 folds tiles i-4 .. i, the first
    # four 1, 2, 3, 4; a full layer the lower triangle
    assert costs.folded_tile_pairs(SHAPE, "sliding") == \
        1 + 2 + 3 + 4 + 28 * 5 == 150
    assert costs.folded_tile_pairs(SHAPE, "full") == 32 * 33 // 2 == 528
    assert 31_458_304 / (150 * 512 ** 2) == pytest.approx(0.800, abs=1e-3)
    assert 134_225_920 / (528 * 512 ** 2) == pytest.approx(0.970, abs=1e-3)
    # a window that is no multiple of the tile, and one wider than S
    odd = dict(SHAPE, seq_len=64, attn_block=16, window=24)
    assert costs.folded_tile_pairs(odd, "sliding") == 1 + 2 + 3 + 3
    assert costs.visible_pairs(dict(odd, window=100), "sliding") == 64 * 65 // 2
    assert costs.folded_tile_pairs(dict(odd, window=100), "sliding") == 10


def test_matrix_params_by_hand():
    p = costs.matrix_params_per_token(SHAPE)
    # wq, wo, wg 2048 x 4096; wk, wv 2048 x 512: 27,262,976 a layer
    assert p["attention"] == 5 * (3 * 2048 * 4096 + 2 * 2048 * 512)
    assert p["dense_ffn"] == 3 * 2048 * 6144
    assert p["route"] == 4 * 2048 * 128
    assert p["shared"] == 4 * 3 * 2048 * 1024
    # half a pick a token lands here in the mean: 8 picks x 8 / 128
    assert p["experts"] == pytest.approx(4 * 0.5 * 3 * 2048 * 1024)
    assert p["head"] == 25024 * 2048
    assert sum(p.values()) == pytest.approx(264.1e6, rel=2e-3)   # ISSUE 37


def test_step_floor_by_hand():
    out = costs.step_floor_seconds(SHAPE, PEAKS)
    scores = 3 * 2 * 2 * (4 * 31_458_304 + 134_225_920) * 32 * 128
    assert costs.attention_score_flops(SHAPE) == scores
    assert scores == pytest.approx(12.8e12, rel=0.01)
    matrices = 6 * 16384 * sum(costs.matrix_params_per_token(SHAPE).values())
    assert out["flops"] == pytest.approx(scores + matrices)
    assert out["flops"] == pytest.approx(38.7e12, rel=0.01)
    assert out["bound"] == "compute"
    assert out["seconds"] == pytest.approx(out["flops"] / 197e12)
    assert out["bytes"] == 32 * 504_147_712


def test_kernels_count_recomputation():
    k = costs.step_floor_seconds(SHAPE, PEAKS)["kernels"]
    rows = 16384 * 8 * 0.0625
    # 4 layers x 3 products x (3 forward runs + 2 backward products)
    assert k["ragged_dot"]["flops"] == pytest.approx(
        4 * 3 * 5 * 2 * rows * 2048 * 1024)
    # tile pairs x layers, 9 products of 2 x 512^2 x 128 for each of 32 heads
    per_pair = 9 * 2 * 512 ** 2 * 128 * 32
    assert k["window_attention"]["flops"] == 150 * 4 * per_pair
    assert k["full_attention"]["flops"] == 528 * 1 * per_pair
    assert k["window_attention"]["flops"] + k["full_attention"]["flops"] \
        == pytest.approx(21.8e12, rel=0.01)                      # ISSUE 37
    for name in ("window_attention", "full_attention"):
        assert k[name]["seconds"] == pytest.approx(
            k[name]["flops"] / 197e12)             # compute-bound


def test_held_share_scales_only_the_routed_experts():
    more = dict(SHAPE, held_pick_share=12.5)
    a, b = (costs.matrix_params_per_token(s) for s in (SHAPE, more))
    assert b["experts"] == 2 * a["experts"]
    assert {k: v for k, v in a.items() if k != "experts"} == \
        {k: v for k, v in b.items() if k != "experts"}


def test_the_mix_is_deterministic_and_covers_the_slice():
    mix = spec.load_json(spec.bench_path("traffic", "zipf-lm-16k-t16k.json"))
    assert (mix["sentence_tokens"], mix["sequences_per_step"]) == (16384, 1)
    small = dict(mix, stream_tokens=65536)
    a, _ = traffic.key_stream(small, 25024, 2 ** 31 + 5)
    b, _ = traffic.key_stream(small, 25024, 2 ** 31 + 5)
    c, _ = traffic.key_stream(small, 25024, 2 ** 31 + 6)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.bincount(a, minlength=25024).min() >= 1    # every id once
    assert len(a) // mix["sentence_tokens"] == 4


def test_benchmark_holds_the_cell_and_its_metrics():
    """No count and no position is pinned: later PRs add cells and a
    ``benchmark`` PR folds entries (PR 51: one entry a reader)."""
    b = spec.load_benchmark()
    assert spec.check() == []
    cells = {w["name"]: w for w in b["workloads"]}
    four = sorted(n for n, w in cells.items() if w["chips"] == 4)
    assert four == ["gnews3m-x4-b16k", "gnews3m-x4-b64k"]
    assert len(four) <= spec.four_chip_quota(len(cells))
    cell = cells["trinity-ep16-16k-t16k"]
    assert (cell["config"], cell["chips"]) == ("trinity-mini-ep16", 1)
    assert "trinity-mini-ep16" in {c["name"] for c in b["configs"]}
    reported = {m["name"] for m, _ in
                spec.load_cell("trinity-ep16-16k-t16k").per_layer}
    assert {"sw.window_attention_ms_per_step", "lm.attention_ms_per_step",
            "lm.route_ms_per_step", "lm.experts_ms_per_step",
            "lm.shared_expert_ms_per_step", "lm.dense_ffn_ms_per_step",
            "lm.head_ms_per_step", "lm.embed_ms_per_step",
            "lm.optimizer_ms_per_step", "lm.unscoped_ms_per_step",
            "sw.window_pair_fill_share", "sw.full_pair_fill_share",
            "lm.held_pick_share", "lm.expert_load_max_over_mean",
            "lm.dropped_picks_per_step", "sw_window_attention_roofline",
            "full_attention_roofline", "ragged_dot_roofline"} <= reported
    # 2 + 14 x cells runs of run_seconds + 60 s, 2 x 90 s a cell, 1200 spare
    runs = 2 + 14 * len(cells)
    assert runs * (b["run_seconds"] + 60) + 180 * len(cells) + 1200 <= 43200
    assert len(cell["why"]) <= 200
