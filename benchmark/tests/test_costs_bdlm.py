"""costs/bdlm.py against a hand count of sdar-30b-a3b-ep8's step."""

import pytest

from benchmark.costs import bdlm as costs

SHAPE = {"tokens": 16384, "seq_len": 8192, "layers": 4, "d_model": 2048,
         "heads": 32, "kv_heads": 4, "d_head": 128, "d_expert": 768,
         "experts": 128, "experts_held": 16, "top_k": 8, "vocab": 18992,
         "diffusion_block": 4, "attn_block": 512, "held_pick_share": 12.5,
         "parameters": 456_346_624, "chips": 1}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_pairs_and_tiles_by_hand():
    # a noised block of 4 sees itself: S x 4; and the clean text before
    # it: 4 x 4 (0 + 1 + ... + 2047) = (S^2 - 4 S) / 2; a clean position the
    # clean text up to its block's end: (S^2 + 4 S) / 2
    S = 8192
    assert costs.visible_pairs(SHAPE) == \
        S * 4 + (S * S - 4 * S) // 2 + (S * S + 4 * S) // 2 == 67_141_632
    # 16 tiles a half: noisy tile i folds i + 2, clean tile i folds i + 1
    assert costs.folded_tile_pairs(SHAPE) == \
        sum(i + 2 for i in range(16)) + sum(i + 1 for i in range(16)) == 288
    assert 288 * 512 ** 2 == 75_497_472            # 88.9 % of them visible
    assert costs.folded_tile_pairs(dict(SHAPE, seq_len=64, attn_block=512)) \
        == 3                                       # one tile a half


def test_matrix_params_by_hand():
    p = costs.matrix_params_per_position(SHAPE)
    # wq 2048 x 4096, wk and wv 2048 x 512, wo 4096 x 2048: 18,874,368
    assert p["attention"] == 4 * (2 * 2048 * 4096 + 2 * 2048 * 512)
    assert p["route"] == 4 * 2048 * 128
    # one pick a position lands here in the mean: 8 picks x 16/128
    assert p["experts"] == pytest.approx(4 * 1.0 * 3 * 2048 * 768)
    assert p["head"] == 18992 * 2048


def test_step_floor_by_hand():
    out = costs.step_floor_seconds(SHAPE, PEAKS)
    scores = 3 * (2 * 2 * 67_141_632 * 32 * 128) * 2 * 4
    assert costs.attention_score_flops(SHAPE) == scores
    assert scores == pytest.approx(26.4e12, rel=0.01)
    trunk = 6 * 32768 * (75_497_472 + 1_048_576 + 18_874_368)
    head = 6 * 16384 * 18992 * 2048
    assert out["flops"] == pytest.approx(scores + trunk + head)
    assert out["flops"] == pytest.approx(49.0e12, rel=0.01)   # ISSUE 33
    assert out["bound"] == "compute"
    assert out["seconds"] == pytest.approx(out["flops"] / 197e12)
    assert out["bytes"] == 32 * 456_346_624


def test_kernels_count_recomputation():
    k = costs.step_floor_seconds(SHAPE, PEAKS)["kernels"]
    rows = 32768 * 8 * 0.125
    # 4 layers x 3 products x (2 forward runs + 2 backward products)
    assert k["ragged_dot"]["flops"] == 4 * 3 * 4 * 2 * rows * 2048 * 768
    # 288 tile pairs x 2 sequences x 4 layers, 9 products of 2 x 512^2 x 128
    # for each of 32 heads
    assert k["attention"]["flops"] == \
        288 * 2 * 4 * 9 * 2 * 512 ** 2 * 128 * 32
    assert k["attention"]["flops"] == pytest.approx(44.5e12, rel=0.01)
    assert k["attention"]["seconds"] == pytest.approx(
        k["attention"]["flops"] / 197e12)          # compute-bound


def test_held_share_scales_only_the_experts():
    more = dict(SHAPE, held_pick_share=25.0)
    a, b = (costs.matrix_params_per_position(s) for s in (SHAPE, more))
    assert b["experts"] == 2 * a["experts"]
    assert {k: v for k, v in a.items() if k != "experts"} == \
        {k: v for k, v in b.items() if k != "experts"}
