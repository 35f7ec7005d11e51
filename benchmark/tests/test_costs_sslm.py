"""costs/sslm.py against a hand count of nemotron-3-nano-30b-a3b-ep16's step,
the new traffic mix's determinism, and BENCHMARK.json with its two four-chip
cells of eleven against the driver's limits."""

import json

import numpy as np
import pytest

from benchmark.costs import sslm as costs
from benchmark.lib import spec, traffic

KINDS = [("ssm", "none"), ("none", "moe"), ("ssm", "none"), ("none", "moe"),
         ("ssm", "none"), ("full", "none"), ("none", "moe"), ("ssm", "none"),
         ("none", "moe")]
SHAPE = {"tokens": 8192, "seq_len": 8192, "kinds": KINDS, "d_model": 2688,
         "heads": 32, "kv_heads": 2, "d_head": 128, "ssm_heads": 64,
         "ssm_head_dim": 64, "ssm_state": 128, "ssm_groups": 8, "kernel": 4,
         "ssm_chunk": 128, "scan_chunks": 256.0, "d_expert": 1856,
         "d_shared": 3712, "experts": 128, "experts_held": 8, "top_k": 6,
         "vocab": 16384, "attn_block": 512, "held_pick_share": 6.25,
         "parameters": 666_963_456, "chips": 1}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MIXER = 2688 * (4096 + 6144 + 64) + 4096 * 2688


def test_parameters_by_hand():
    """ISSUE 46's count, part by part."""
    assert costs.mixer_params(SHAPE) == MIXER == 27_697_152 + 11_010_048
    per = costs.layer_parameters(SHAPE)
    # taps 24,576 + bias 6,144; A_log, dt_bias, D 64 each; gain 4,096; the
    # layer's norm 2,688
    assert per["ssm"] == MIXER + 24_576 + 6_144 + 192 + 4_096 + 2_688 \
        == 38_744_896
    assert costs.attention_params(SHAPE) == 2 * 11_010_048 + 2 * 688_128
    assert per["full"] == 23_396_352 + 2_688 == 23_399_040
    expert = 2 * 2688 * 1856
    assert expert == 9_977_856
    assert per["moe"] == 344_064 + 128 + 19_955_712 + 8 * expert + 2_688 \
        == 100_125_440
    total = 4 * per["ssm"] + 4 * per["moe"] + per["full"] \
        + 2 * 44_040_192 + 2_688
    assert total == costs.parameters(SHAPE) == 666_963_456
    assert costs.step_floor_seconds(SHAPE, PEAKS)["bytes"] == 32 * total


def test_scan_work_by_hand():
    # a chunk of 128 positions: c.b 2 x 128^2 x 128 x 8 groups; the weighted
    # scores times x 2 x 128^2 x 64 x 64 heads; the chunk's state and the
    # entering state's read-out 2 x 128 x 64 x 128 x 64 heads each
    per_chunk = 33_554_432 + 134_217_728 + 2 * 134_217_728
    assert costs.scan_chunk_flops(SHAPE) == per_chunk == 436_207_616
    work = costs.step_floor_seconds(SHAPE, PEAKS)["kernels"]["ssm_scan"]
    # 4 layers x 64 chunks, 4 forwards' worth (forward, recomputation, a
    # backward of two)
    assert work["flops"] == 4 * 256 * per_chunk == pytest.approx(4.47e11,
                                                                 rel=1e-3)
    # x, y 2 x 128 x 4,096; B, C 2 x 128 x 1,024; dt 128 x 64; the state
    # written and read 2 x 64 x 64 x 128: float32
    chunk_bytes = 4 * (1_048_576 + 262_144 + 8_192 + 1_048_576)
    assert work["bytes"] == 4 * 256 * chunk_bytes
    assert work["seconds"] == pytest.approx(work["bytes"] / 819e9)  # memory
    assert work["seconds"] > work["flops"] / 197e12
    # the counted chunks scale it
    half = costs.scan_work(dict(SHAPE, scan_chunks=128.0))
    assert half["flops"] == work["flops"] / 2
    # a sequence shorter than a chunk is one chunk of its own length
    assert costs.scan_chunk_flops(dict(SHAPE, seq_len=64)) < per_chunk / 2


def test_matrix_params_and_step_floor_by_hand():
    p = costs.matrix_params_per_token(SHAPE)
    assert p["ssm_mixer"] == 4 * MIXER
    assert p["attention"] == 23_396_352
    assert p["route"] == 4 * 2688 * 128
    assert p["shared"] == 4 * 2 * 2688 * 3712
    # 6 picks x 8 / 128 = 0.375 of a pick a token lands here in the mean
    assert p["experts"] == pytest.approx(4 * 0.375 * 2 * 2688 * 1856)
    assert p["head"] == 16384 * 2688
    # ISSUE 46's forward MFLOP a token: 4 x 77 state-space, 47 attention
    # projections, 88 the head
    assert 2 * MIXER == pytest.approx(77.4e6, rel=1e-3)
    assert 2 * p["attention"] == pytest.approx(46.8e6, rel=1e-3)
    assert 2 * p["head"] == pytest.approx(88.1e6, rel=1e-3)
    out = costs.step_floor_seconds(SHAPE, PEAKS)
    scores = 3 * 2 * 2 * (8192 * 8193 // 2) * 32 * 128
    assert costs.attention_score_flops(SHAPE) == scores
    assert out["flops"] == pytest.approx(
        scores + 6 * 8192 * sum(p.values()) + 3 * 256 * 436_207_616)
    assert out["bound"] == "compute"
    assert out["seconds"] == pytest.approx(out["flops"] / 197e12)
    # useful work is under what the step executes (ISSUE 46's ~23 TFLOP)
    assert 12e12 < out["flops"] < 23e12


def test_kernels_by_hand():
    k = costs.step_floor_seconds(SHAPE, PEAKS)["kernels"]
    assert set(k) == {"ragged_dot", "ssm_scan", "ssm_mixer",
                      "full_attention"}
    rows = 8192 * 6 * 0.0625
    # 4 expert layers x 2 products x (2 forward runs + 2 backward products)
    assert k["ragged_dot"]["flops"] == pytest.approx(
        4 * 2 * 4 * 2 * rows * 2688 * 1856)
    # the mixers' projections: 4 layers x 4 products of 2 x 38.7 M x 8,192
    assert k["ssm_mixer"]["flops"] == 4 * 4 * 2.0 * MIXER * 8192
    assert k["ssm_mixer"]["seconds"] == pytest.approx(
        k["ssm_mixer"]["flops"] / 197e12)                # compute-bound
    # the one attention layer's tile loop: 136 pairs, 9 products of
    # 2 x 512^2 x 128 x 32 heads
    assert k["full_attention"]["flops"] == pytest.approx(
        136 * 9 * 2 * 512 * 512 * 128 * 32)
    more = dict(SHAPE, held_pick_share=12.5)
    a, b = (costs.matrix_params_per_token(s) for s in (SHAPE, more))
    assert b["experts"] == 2 * a["experts"]
    assert {n: v for n, v in a.items() if n != "experts"} == \
        {n: v for n, v in b.items() if n != "experts"}


def test_the_mix_is_deterministic_and_covers_the_slice():
    mix = spec.load_json(spec.bench_path("traffic", "zipf-ssm-8k-t8k.json"))
    assert (mix["sentence_tokens"], mix["sequences_per_step"]) == (8192, 1)
    assert (mix["stream_tokens"], mix["chunk_steps"], mix["warmup_chunks"],
            mix["trace_chunks"], mix["eval_tokens"]) == \
        (4194304, 2, 2, 2, 8192)
    assert mix["keys"] == {"distribution": "zipf", "exponent": 1.0,
                           "every_key_once": True}
    small = dict(mix, stream_tokens=65536)
    a, _ = traffic.key_stream(small, 16384, 2 ** 31 + 5)
    b, _ = traffic.key_stream(small, 16384, 2 ** 31 + 5)
    c, _ = traffic.key_stream(small, 16384, 2 ** 31 + 6)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.bincount(a, minlength=16384).min() >= 1    # every id once
    assert len(a) // mix["sentence_tokens"] == 8


def test_the_configuration_is_the_catalogs_row_but_for_the_cut():
    config = spec.load_json(spec.bench_path(
        "configs", "nemotron-3-nano-30b-a3b-ep16.json"))
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert {k: config[k] for k in config["reduced"]} == {
        "num_hidden_layers": 9, "n_routed_experts": 8, "vocab_size": 16384}
    assert config["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072}
    # every width as published
    for key, value in {
            "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
            "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
            "chunk_size": 128, "num_attention_heads": 32,
            "num_key_value_heads": 2, "head_dim": 128,
            "moe_intermediate_size": 1856, "intermediate_size": 1856,
            "moe_shared_expert_intermediate_size": 3712,
            "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
            "expand": 2, "mlp_hidden_act": "relu2"}.items():
        assert config[key] == value, key
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == 52 and (pattern.count("M"), pattern.count("E"),
                                   pattern.count("*")) == (23, 23, 6)
    assert "".join(pattern[i] for i in config["layers_held"]) == "MEMEM*EME"
    lo, hi = config["experts_held"]
    assert hi - lo == 8 and lo % 8 == 0 and hi <= 128
    assert config["train"]["ssm_chunk"] == config["chunk_size"]
    assert config["precision"]["ssm_decay_state_and_sums"] == "float32"
    assert len(json.dumps(config)) < 64 * 1024


def test_benchmark_with_two_four_chip_cells_of_eleven():
    b = spec.load_benchmark()
    assert spec.check() == []
    cells = {w["name"]: w for w in b["workloads"]}
    assert len(cells) >= 11 and len(b["configs"]) >= 8
    assert sorted(n for n, w in cells.items() if w["chips"] == 4) == \
        ["gnews3m-x4-b16k", "gnews3m-x4-b64k"]
    assert 2 <= spec.four_chip_quota(len(cells))
    cell = cells["nemotron3n-ep16-8k-t8k"]
    assert cell == {
        "name": "nemotron3n-ep16-8k-t8k",
        "config": "nemotron-3-nano-30b-a3b-ep16",
        "traffic": "zipf-ssm-8k-t8k", "chips": 1, "why": cell["why"]}
    entry = next(c for c in b["configs"]
                 if c["name"] == "nemotron-3-nano-30b-a3b-ep16")
    # no count and no position is pinned: PR 51 folded the entries that
    # shared a reader, so the cell's shared scopes read under lm.* names
    reported = {m["name"] for m, _ in
                spec.load_cell("nemotron3n-ep16-8k-t8k").per_layer}
    assert {"ssm.mixer_ms_per_step", "ssm.scan_ms_per_step",
            "ssm.scan_chunks_per_step", "ssm_scan_roofline",
            "ssm_mixer_roofline", "full_attention_roofline",
            "ragged_dot_roofline", "lm.attention_ms_per_step",
            "lm.route_ms_per_step", "lm.experts_ms_per_step",
            "lm.shared_expert_ms_per_step", "lm.head_ms_per_step",
            "lm.embed_ms_per_step", "lm.optimizer_ms_per_step",
            "lm.unscoped_ms_per_step", "lm.held_pick_share",
            "lm.expert_load_max_over_mean",
            "lm.dropped_picks_per_step"} <= reported
    # 2 + 14 x cells runs of run_seconds + 60 s, 2 x 90 s a cell, 1200 spare
    runs = 2 + 14 * len(cells)
    assert runs * (b["run_seconds"] + 60) + 180 * len(cells) + 1200 <= 43200
    for c in (entry, cell):
        assert len(c["why"]) <= 200
