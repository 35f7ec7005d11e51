"""Tiny-shape drives of bench.py's measurement cells whose first real
execution would otherwise happen on the chip, where debugging is the
expensive way to spend a budget.  Shapes are
monkeypatched down; semantics (modes, labels, finiteness) are pinned,
not performance."""

import os
import sys

import numpy as np
import pytest

REPO = __file__.rsplit("/tests/", 1)[0]
sys.path.insert(0, REPO)

jax = pytest.importorskip("jax")

import bench  # noqa: E402
from swiftmpi_tpu.data import native  # noqa: E402

needs_native = pytest.mark.skipif(
    not native.available(), reason="native loader not built")


@pytest.fixture
def tiny_shapes(monkeypatch):
    # demo-parity subsampling (sample=1e-5) keeps only a few % of toy
    # tokens as centers — corpus sized so a couple of full 256-center
    # batches survive
    monkeypatch.setattr(bench, "BATCH", 256)
    monkeypatch.setattr(bench, "INNER_STEPS", 2)
    monkeypatch.setattr(bench, "SENTENCES", 300)
    monkeypatch.setattr(bench, "SENT_LEN", 80)
    monkeypatch.setattr(bench, "VOCAB", 400)
    for var in [v for v in os.environ if v.startswith("BENCH_")]:
        monkeypatch.delenv(var, raising=False)


@needs_native
def test_fused_epoch_cell_tiny(tiny_shapes, monkeypatch):
    """BENCH_EPOCH_FUSED=1: whole epoch in one donated dispatch —
    label, batch accounting, and a sane loss at toy shape."""
    monkeypatch.setenv("BENCH_EPOCH_FUSED", "1")
    dev = jax.devices()[0]
    model, _, _ = bench._build_w2v(dev)
    out = bench._bench_w2v_epoch(dev, model)
    assert out["mode"] == "fused_epoch"
    assert out["n_batches"] >= 1
    assert out["corpus_tokens"] == 300 * 80
    assert out["epoch_wall_s"] > 0
    assert np.isfinite(out["loss"]) and out["loss"] > 0


@needs_native
def test_100m_cell_tiny(tiny_shapes, monkeypatch):
    """BASELINE config #3 cell at smoke shape: streaming epoch through
    the native loader with the async (local_steps=4) path — labels,
    loader accounting, finite loss.  The real 100M-token shape runs via
    scripts/config3_scale.py (CPU) / BENCH_100M=1 bench.py (TPU)."""
    monkeypatch.setenv("BENCH_100M_SENTS", "300")
    monkeypatch.setenv("BENCH_100M_VOCAB", "500")
    monkeypatch.setenv("BENCH_100M_LEN", "80")
    dev = jax.devices()[0]
    out = bench._bench_w2v_100m(dev)
    assert out["corpus_tokens"] == 300 * 80
    assert out["local_steps"] == 4
    assert out["loader_tokens_per_sec"] > 0
    assert out["vocab"] > 100
    assert out["epoch_wall_s"] > 0
    assert np.isfinite(out["loss"]) and out["loss"] > 0


@needs_native
def test_public_epoch_cell_tiny(tiny_shapes):
    """The public-path epoch cell (the A/B's other arm) at the same
    toy shape: no mode label, same token accounting, and the model's
    tail-fuse freeze is released afterwards."""
    dev = jax.devices()[0]
    model, _, _ = bench._build_w2v(dev)
    out = bench._bench_w2v_epoch(dev, model)
    assert "mode" not in out
    assert out["corpus_tokens"] == 300 * 80
    assert out["epoch_wall_s"] > 0
    assert model._tail_fuse_frozen is False


def test_scale_shared_cell_tiny(tiny_shapes, monkeypatch):
    """BENCH_SCALE_SHARED=1: the 1M cell switches to the batch-shared
    negative-pool rendering (the r5 phase profile pins the per-pair
    cell on its B*(K+1)-row push) and the output labels itself — the
    merged w2v_1m_shared cell must be distinguishable by content from
    the per-pair w2v_1m cell."""
    monkeypatch.setattr(bench, "W2V_1M_VOCAB", 5000)
    monkeypatch.setenv("BENCH_SCALE_SHARED", "1")
    dev = jax.devices()[0]
    out = bench._bench_w2v_1m(dev, timed_calls=1)
    assert out["rendering"] == "shared"
    assert out["vocab"] == 5000
    assert out["words_per_sec"] > 0
    # and without the env the per-pair rendering stays the default
    monkeypatch.delenv("BENCH_SCALE_SHARED")
    out2 = bench._bench_w2v_1m(dev, timed_calls=1)
    assert out2["rendering"] in ("gather", None)


def test_tfm_cell_knobs_tiny(tiny_shapes, monkeypatch):
    """BENCH_TFM_{SEQ,DMODEL,LAYERS} (r5d MFU sweep): the cell honors
    the model-size knobs, derives a head count that divides d_model
    even for non-64-multiples, and the record self-describes its shape
    (a sweep cell whose config is unrecoverable cannot be compared)."""
    monkeypatch.setenv("BENCH_TFM_BATCH", "2")
    monkeypatch.setenv("BENCH_TFM_SEQ", "16")
    monkeypatch.setenv("BENCH_TFM_DMODEL", "40")  # 40//64 -> 1 head
    monkeypatch.setenv("BENCH_TFM_LAYERS", "1")
    monkeypatch.setenv("BENCH_TFM_REMAT", "1")
    out = bench._bench_tfm(jax.devices()[0], timed_calls=1)
    assert (out["batch"], out["seq"]) == (2, 16)
    assert (out["d_model"], out["n_layers"], out["d_ff"]) == (40, 1, 160)
    assert out["d_model"] % out["n_heads"] == 0
    assert out["remat"] is True
    assert out["tokens_per_sec"] > 0 and np.isfinite(out["loss"])


def test_scale_stencil_cell_tiny(tiny_shapes, monkeypatch):
    """BENCH_ONLY=scale_stencil's cell: the positional-stencil rendering
    composed with the shared negative pool at (shrunk) 1M-vocab shape —
    labels itself stencil_shared, records the span working set
    (B + 2W), and produces a finite rate with an HBM bytes model."""
    monkeypatch.setattr(bench, "W2V_1M_VOCAB", 5000)
    dev = jax.devices()[0]
    out = bench._bench_w2v_1m(dev, timed_calls=1, stencil=True)
    assert out["rendering"] == "stencil_shared"
    assert out["span"] == bench.BATCH + 8          # window 4 -> 2W = 8
    assert out["vocab"] == 5000
    assert out["words_per_sec"] > 0
    # the stencil branch of the step-bytes model resolves (non-None)
    model, _ = bench.build_w2v_1m_model(dev, stencil=True)
    model._build_multi_step(2)
    assert bench._w2v_step_bytes(model, bench.BATCH) is not None


def test_scale_hybrid_cell_tiny(tiny_shapes, monkeypatch):
    """BENCH_ONLY=scale_hybrid's cell: ``transfer=hybrid`` over the
    stencil+pool rendering at (shrunk) 1M-vocab shape — labels the
    transfer, reports the replicated head size, and carries the
    per-step traffic ledger (routed vs hot rows, psum bytes) the cell
    exists to measure."""
    monkeypatch.setattr(bench, "W2V_1M_VOCAB", 5000)
    dev = jax.devices()[0]
    out = bench._bench_w2v_1m(dev, timed_calls=1, hybrid=True)
    assert out["rendering"] == "stencil_shared"
    assert out["transfer"] == "hybrid"
    assert out["hot_head_rows"] > 0
    assert out["words_per_sec"] > 0
    # traffic counters were armed before the jit build, so both the
    # replicated-head and routed-tail paths recorded real rows
    assert out["hot_rows_per_step"] > 0
    assert out["routed_rows_per_step"] > 0
    assert out["psum_bytes_per_step"] > 0
    assert out["overflow_dropped"] == 0


def test_tfm_odd_head_dim_fails_fast(tiny_shapes, monkeypatch):
    """BENCH_TFM_DMODEL values whose derived head_dim is odd must fail
    up front with a clear message, not crash _rope at trace time after
    the build.  129 -> H=1, hd=129; even
    d_model is not enough: 130 -> H=2, hd=65."""
    for dm in ("129", "130"):
        monkeypatch.setenv("BENCH_TFM_DMODEL", dm)
        with pytest.raises(ValueError, match="head_dim"):
            bench._bench_tfm(jax.devices()[0], timed_calls=1)
    # the guard admits valid shapes (the existing D=40 sweep point)
    monkeypatch.setenv("BENCH_TFM_BATCH", "2")
    monkeypatch.setenv("BENCH_TFM_SEQ", "16")
    monkeypatch.setenv("BENCH_TFM_DMODEL", "40")
    monkeypatch.setenv("BENCH_TFM_LAYERS", "1")
    out = bench._bench_tfm(jax.devices()[0], timed_calls=1)
    assert out["tokens_per_sec"] > 0


def test_scale_qwire_cell_tiny(tiny_shapes, monkeypatch):
    """BENCH_ONLY=scale_qwire's cell: the window shape with [cluster]
    wire_quant armed at (shrunk) 1M-vocab scale — self-describes the
    quant mode, carries the 4-way decision-mix counters the budget
    gate's sanity floor reads, and books a finite encoded wire ledger."""
    monkeypatch.setattr(bench, "W2V_1M_VOCAB", 5000)
    dev = jax.devices()[0]
    out = bench._bench_w2v_1m(dev, timed_calls=1, hybrid=True,
                              window_steps=2, wire_quant="int8")
    assert out["wire_quant"] == "int8"
    assert out["push_window"] == 2
    assert out["words_per_sec"] > 0
    fmts = [out[f"window_fmt_{f}"]
            for f in ("dense", "sparse", "q", "bitmap")]
    assert all(v >= 0 for v in fmts) and sum(fmts) > 0
    assert out["wire_bytes_per_step"] > 0
    # (quant-off self-description is pinned cheaply at unit level by
    # test_window_push.py::test_wire_quant_off_bit_identity_all_backends
    # — a second tiny bench build here would double the cell's cost)


def test_scale_sketchwire_cell_tiny(tiny_shapes, monkeypatch):
    """BENCH_ONLY=scale_sketchwire's cell: the qwire shape with
    [cluster] wire_sketch armed on top — self-describes both knobs,
    carries the full 5-way decision mix plus the TrafficPlan compile
    counters, and embeds the static d=1/d=32 mid-density pricing
    evidence the cell exists to publish."""
    monkeypatch.setattr(bench, "W2V_1M_VOCAB", 5000)
    dev = jax.devices()[0]
    out = bench._bench_w2v_1m(dev, timed_calls=1, hybrid=True,
                              window_steps=2, wire_quant="int8",
                              wire_sketch=True)
    assert out["wire_quant"] == "int8"
    assert out["wire_sketch"] == 1
    assert out["push_window"] == 2
    assert out["words_per_sec"] > 0
    fmts = [out[f"window_fmt_{f}"]
            for f in ("dense", "sparse", "q", "bitmap", "sketch")]
    assert all(v >= 0 for v in fmts) and sum(fmts) > 0
    assert out["wire_bytes_per_step"] > 0
    # every armed window decision flowed through the ONE plan compiler
    assert out["plan_compiles"] + out["plan_cache_hits"] > 0
    ev = bench._sketch_price_evidence()
    # d=1 mid-density shape: the sketch rung strictly undercuts the
    # best lossless alternative AND survives the sparse_q guard — the
    # crossover the fifth rung was added to win
    assert ev["d1"]["decision"] == "sparse_sketch"
    assert ev["d1"]["sketch_below_best_lossless"]
    assert ev["d1"]["sparse_sketch"] < min(ev["d1"]["sparse"],
                                           ev["d1"]["bitmap"],
                                           ev["d1"]["sparse_q"])
    # d=32: still below every lossless rung; int8 sparse_q takes the
    # overall pick (the documented lossless/lossy guard boundary)
    assert ev["d32"]["sketch_below_best_lossless"]
    assert ev["d32"]["decision"] == "sparse_q"


def test_bench_without_tpu_fails_in_one_process(tmp_path):
    """``python bench.py`` on a host with no TPU: non-zero exit, no
    number on stdout, and no child process started (the chip belongs to
    one process — nothing on the measurement path may spawn another)."""
    import subprocess

    marker = tmp_path / "spawned"
    prog = (
        "import runpy, subprocess, sys\n"
        "def boom(*a, **k):\n"
        f"    open({str(marker)!r}, 'w').close()\n"
        "    raise AssertionError('bench.py started a subprocess')\n"
        "subprocess.Popen = boom\n"
        "sys.argv = ['bench.py']\n"
        f"runpy.run_path({os.path.join(REPO, 'bench.py')!r}, "
        "run_name='__main__')\n")
    res = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=300, cwd=str(tmp_path),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert res.stdout.strip() == ""
    assert not marker.exists()


def test_roofline_unknown_tpu_kind_raises():
    """A TPU that is not in the peaks table is an error, not a default."""
    class Dev:
        platform = "tpu"
        device_kind = "TPU v99"

    with pytest.raises(KeyError, match="TPU v99"):
        bench._roofline(Dev(), 1e-3, hbm_bytes=1e6)
