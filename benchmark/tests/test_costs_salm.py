"""costs/salm.py against a hand count of keye-vl-2.0-30b-a3b-ep8's step."""

import pytest

from benchmark.costs import salm as costs

SHAPE = {"tokens": 16384, "seq_len": 16384, "layers": 4, "d_model": 2048,
         "heads": 32, "kv_heads": 4, "d_head": 128, "d_expert": 768,
         "experts": 128, "experts_held": 16, "top_k": 8, "vocab": 18992,
         "index_heads": 16, "index_dim": 64, "index_topk": 2048,
         "attn_block": 512, "held_pick_share": 12.5,
         "selected_keys_per_query": 31_458_304 / 16384,
         "parameters": 465_391_104, "chips": 1}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_pairs_by_hand():
    S, k = 16384, 2048
    # queries 0..2047 keep all their keys, the 14,336 after them 2,048 each
    kept = k * (k + 1) // 2 + (S - k) * k
    assert kept == sum(min(t + 1, k) for t in range(S)) == 31_458_304
    assert costs.selected_pairs(SHAPE) == kept
    assert costs.causal_pairs(SHAPE) == S * (S + 1) // 2 == 134_225_920
    assert kept / 134_225_920 == pytest.approx(0.2344, abs=1e-4)
    assert SHAPE["selected_keys_per_query"] == pytest.approx(1920.0625)


def test_matrix_params_by_hand():
    p = costs.matrix_params_per_token(SHAPE)
    # wq 2048 x 4096, wk and wv 2048 x 512, wo 4096 x 2048: 18,874,368
    assert p["sparse_attention"] == 4 * (2 * 2048 * 4096 + 2 * 2048 * 512)
    # wq_idx 2048 x 1024, wk_idx 2048 x 64, w_idx 2048 x 16: 2,260,992
    assert p["indexer"] == 4 * (2_097_152 + 131_072 + 32_768)
    assert p["route"] == 4 * 2048 * 128
    assert p["experts"] == pytest.approx(4 * 1.0 * 3 * 2048 * 768)
    assert p["head"] == 18992 * 2048


def test_step_floor_by_hand():
    out = costs.step_floor_seconds(SHAPE, PEAKS)
    attention = 3 * (2 * 2 * 31_458_304 * 32 * 128) * 4
    index = 3 * (2 * 134_225_920 * 16 * 64) * 4
    assert costs.attention_forward_flops(SHAPE) * 3 == attention
    assert costs.index_product_flops(SHAPE) * 3 == index
    per_token = 75_497_472 + 9_043_968 + 1_048_576 + 18_874_368 + 38_895_616
    assert out["flops"] == pytest.approx(6 * 16384 * per_token
                                         + attention + index)
    assert out["bound"] == "compute"
    assert out["seconds"] == pytest.approx(out["flops"] / 197e12)
    assert out["bytes"] == 32 * 465_391_104


def test_kernels_credit_the_selected_pairs_and_one_index_walk_each_way():
    k = costs.step_floor_seconds(SHAPE, PEAKS)["kernels"]
    rows = 16384 * 8 * 0.125
    # 4 layers x 3 products x (2 forward runs + 2 backward products)
    assert k["ragged_dot"]["flops"] == 4 * 3 * 4 * 2 * rows * 2048 * 768
    # the selected pairs' two products, four forwards' worth, 4 layers
    assert k["sparse_attention"]["flops"] == \
        4 * (2 * 2 * 31_458_304 * 32 * 128) * 4
    # a dense causal walk multiplies 4.27 x the pairs the selection keeps
    assert 134_225_920 / 31_458_304 == pytest.approx(4.267, abs=1e-3)
    assert k["indexer"]["flops"] == 3 * (2 * 134_225_920 * 16 * 64) * 4
    for work in k.values():
        assert work["seconds"] == pytest.approx(max(
            work["flops"] / 197e12, work["bytes"] / 819e9))
    # a counted selection that kept fewer pairs is credited with fewer
    fewer = costs.step_floor_seconds(
        dict(SHAPE, selected_keys_per_query=960.0), PEAKS)["kernels"]
    assert fewer["sparse_attention"]["flops"] == pytest.approx(
        k["sparse_attention"]["flops"] * 960.0 / 1920.0625)
