"""costs/mlalm.py against a hand count of glm-4.7-flash-ep8's step, the new
traffic mix's determinism, and BENCHMARK.json with its two four-chip cells
of ten against the driver's limits."""

import numpy as np
import pytest

from benchmark.costs import mlalm as costs
from benchmark.lib import spec, traffic

KINDS = [("latent", "dense")] + [("latent", "moe")] * 4
SHAPE = {"tokens": 8192, "seq_len": 8192, "kinds": KINDS, "mtp": 1,
         "d_model": 2048, "heads": 20, "q_rank": 768, "kv_rank": 512,
         "nope": 192, "rope": 64, "v_dim": 256, "d_ff": 10240,
         "d_expert": 1536, "d_shared": 1536, "experts": 64,
         "experts_held": 8, "top_k": 4, "vocab": 19360, "attn_block": 512,
         "held_pick_share": 12.5, "parameters": 706_518_848, "chips": 1}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LATENT = 1_572_864 + 3_932_160 + 1_179_648 + 4_587_520 + 10_485_760


def test_parameters_by_hand():
    """ISSUE 42's count, part by part."""
    assert costs.latent_params(SHAPE) == LATENT == 21_757_952
    attn = LATENT + 768 + 512                      # the two inner gains
    assert attn == 21_759_232
    dense = attn + 4096 + 3 * 2048 * 10240
    assert dense == 84_677_888
    expert = attn + 4096 + 131_072 + 64 + 9_437_184 + 8 * 9_437_184
    assert expert == 106_829_120
    module = 8_388_608 + 3 * 2048 + expert
    assert module == 115_223_872
    total = dense + 4 * expert + 2 * 19360 * 2048 + 2048 + module
    assert total == costs.parameters(SHAPE) == 706_518_848
    # without the module: the driver's own count of the cut
    assert costs.parameters(dict(SHAPE, mtp=0)) == 591_294_976
    assert costs.step_floor_seconds(SHAPE, PEAKS)["bytes"] == 32 * total


def test_tile_pairs_and_a_pairs_flops_by_hand():
    # 16 tiles of 512: the lower triangle
    assert costs.folded_tile_pairs(SHAPE) == 16 * 17 // 2 == 136
    # QK^T and PV of one pair, forward: 2 x 2 x 512 x 512 x 256 x 20
    per_pair = 2 * 2 * 512 * 512 * 256 * 20
    assert per_pair == pytest.approx(5.37e9, rel=1e-3)
    k = costs.step_floor_seconds(SHAPE, PEAKS)["kernels"]
    # 9 products a pair (forward, recomputation, backward: 4.5 x forward's
    # two), 136 pairs, the stack's five layers; the five projections four
    # times (forward, recomputation, two backward products)
    loops = 5 * 136 * 4.5 * per_pair
    assert loops == pytest.approx(5 * 3.29e12, rel=2e-3)
    proj = 5 * 4 * 2 * LATENT * 8192
    assert k["latent_attention"]["flops"] == pytest.approx(loops + proj)
    assert (loops + proj) * 6 / 5 == pytest.approx(19.7e12 + 8.6e12,
                                                   rel=5e-3)   # ISSUE 42
    assert k["latent_attention"]["seconds"] == pytest.approx(
        k["latent_attention"]["flops"] / 197e12)         # compute-bound


def test_matrix_params_and_step_floor_by_hand():
    p = costs.matrix_params_per_token(SHAPE)
    assert p["latent_attention"] == 5 * LATENT
    assert p["dense_ffn"] == 3 * 2048 * 10240
    assert p["route"] == 4 * 2048 * 64
    assert p["shared"] == 4 * 3 * 2048 * 1536
    # half a pick a token lands here in the mean: 4 picks x 8 / 64
    assert p["experts"] == pytest.approx(4 * 0.5 * 3 * 2048 * 1536)
    assert p["head"] == 19360 * 2048
    assert p["mtp"] == pytest.approx(
        2 * 2048 * 2048 + LATENT + 19360 * 2048
        + 2048 * 64 + 3 * 2048 * 1536 + 0.5 * 3 * 2048 * 1536)
    out = costs.step_floor_seconds(SHAPE, PEAKS)
    scores = 3 * 2 * (8192 * 8193 // 2) * 20 * 512 * 6
    assert costs.attention_score_flops(SHAPE) == scores
    assert out["flops"] == pytest.approx(scores + 6 * 8192 * sum(p.values()))
    assert out["bound"] == "compute"
    assert out["seconds"] == pytest.approx(out["flops"] / 197e12)
    # useful work is under what the step executes (ISSUE 42's ~42 TFLOP)
    assert 20e12 < out["flops"] < 42e12


def test_ragged_dot_counts_the_modules_layer():
    k = costs.step_floor_seconds(SHAPE, PEAKS)["kernels"]
    rows = 8192 * 4 * 0.125
    # 5 expert layers x 3 products x (2 forward runs + 2 backward products)
    assert k["ragged_dot"]["flops"] == pytest.approx(
        5 * 3 * 4 * 2 * rows * 2048 * 1536)
    more = dict(SHAPE, held_pick_share=25.0)
    a, b = (costs.matrix_params_per_token(s) for s in (SHAPE, more))
    assert b["experts"] == 2 * a["experts"]
    assert {k: v for k, v in a.items() if k not in ("experts", "mtp")} == \
        {k: v for k, v in b.items() if k not in ("experts", "mtp")}


def test_the_mix_is_deterministic_and_covers_the_slice():
    mix = spec.load_json(spec.bench_path("traffic", "zipf-lm-8k-t8k.json"))
    assert (mix["sentence_tokens"], mix["sequences_per_step"]) == (8192, 1)
    assert (mix["stream_tokens"], mix["chunk_steps"], mix["warmup_chunks"],
            mix["trace_chunks"]) == (4194304, 2, 2, 2)
    small = dict(mix, stream_tokens=65536)
    a, _ = traffic.key_stream(small, 19360, 2 ** 31 + 5)
    b, _ = traffic.key_stream(small, 19360, 2 ** 31 + 5)
    c, _ = traffic.key_stream(small, 19360, 2 ** 31 + 6)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.bincount(a, minlength=19360).min() >= 1    # every id once
    assert len(a) // mix["sentence_tokens"] == 8


def test_benchmark_with_two_four_chip_cells_of_ten():
    b = spec.load_benchmark()
    assert spec.check() == []
    cells = {w["name"]: w for w in b["workloads"]}
    assert len(cells) >= 10 and len(b["configs"]) >= 7
    assert sorted(n for n, w in cells.items() if w["chips"] == 4) == \
        ["gnews3m-x4-b16k", "gnews3m-x4-b64k"]
    assert 2 <= spec.four_chip_quota(len(cells))
    cell = cells["glm47f-ep8-8k-t8k"]
    assert (cell["config"], cell["chips"]) == ("glm-4.7-flash-ep8", 1)
    entry = next(c for c in b["configs"] if c["name"] == "glm-4.7-flash-ep8")
    # no count and no position is pinned: PR 51 folded the entries that
    # shared a reader, so the cell's shared scopes read under lm.* names
    reported = {m["name"] for m, _ in
                spec.load_cell("glm47f-ep8-8k-t8k").per_layer}
    assert {"mla.latent_attention_ms_per_step", "mla.mtp_ms_per_step",
            "mla.mtp_loss_share", "mla_latent_attention_roofline",
            "lm.route_ms_per_step", "lm.experts_ms_per_step",
            "lm.shared_expert_ms_per_step", "lm.dense_ffn_ms_per_step",
            "lm.head_ms_per_step", "lm.embed_ms_per_step",
            "lm.optimizer_ms_per_step", "lm.unscoped_ms_per_step",
            "lm.held_pick_share", "lm.expert_load_max_over_mean",
            "lm.dropped_picks_per_step", "ragged_dot_roofline"} <= reported
    # 2 + 14 x cells runs of run_seconds + 60 s, 2 x 90 s a cell, 1200 spare
    runs = 2 + 14 * len(cells)
    assert runs * (b["run_seconds"] + 60) + 180 * len(cells) + 1200 <= 43200
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    config = spec.load_json(spec.bench_path("configs",
                                            "glm-4.7-flash-ep8.json"))
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) \
        == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
