"""chip_smoke.py's phases at toy size on the CPU mesh (the script itself
always demands the chip — on-chip-measurement guide: make the command
run here first, then send the same command at the real size)."""

import json
import os
import subprocess
import sys

import numpy as np

REPO = __file__.rsplit("/tests/", 1)[0]
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_phases_run_at_toy_size(tmp_path, monkeypatch, devices8):
    """Every phase — corpus, conf, loader, build, train (single-step
    and fused scan), row / placement checks, save + read-back — through
    the same ``run`` the chip executes at full width."""
    # an externally placed cache directory is left alone (and this test
    # must not arm the persistent cache for the rest of the session)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    out = chip_smoke.run(
        str(tmp_path / "out"), "cpu", vocab_size=2000, zipf_tokens=20_000,
        max_tokens=None, fused_tokens=6000, len_vec=16)
    assert out["loader"] in ("native", "python")
    assert len(out["losses"]) == 3 and len(out["fused_losses"]) == 2
    assert np.isfinite(out["losses"]).all()
    assert out["losses"][-1] < out["losses"][0]
    # 4 fields x capacity x len_vec x f32, split over the 8-device mesh
    assert out["table_bytes"] % (4 * 16 * 4 * 8) == 0
    assert out["peak_bytes"] is None          # XLA:CPU reports none
    # the embeddings went through a pipe: nothing large is left on disk
    assert sorted(os.listdir(tmp_path / "out")) == ["corpus.txt",
                                                    "smoke.conf"]


def test_check_rows_catches_an_untouched_context_row(tmp_path, devices8):
    """The touched-row check must fail when training moved nothing."""
    import pytest

    corpus = str(tmp_path / "c.txt")
    conf = str(tmp_path / "s.conf")
    chip_smoke.write_corpus(corpus, 300, 2000)
    chip_smoke.write_conf(conf, len_vec=8)
    vocab, tokens, offsets, _ = chip_smoke.load_corpus(corpus, 1)
    model = chip_smoke.build_model(conf, vocab)
    touched, untouched = chip_smoke.pick_slots(model, vocab, tokens, n=16)
    before_t = chip_smoke.sample_rows(model, touched)
    before_u = chip_smoke.sample_rows(model, untouched)
    with pytest.raises(SystemExit, match="did not change"):
        chip_smoke.check_rows(model, touched, untouched, before_t,
                              before_u)


def test_script_demands_the_chip():
    """``python chip_smoke.py`` on a host without a TPU: non-zero exit,
    no result line."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    for line in res.stdout.splitlines():
        if line.startswith("{"):
            assert not json.loads(line).get("ok"), line


def test_python_loader_path_agrees_with_native(tmp_path, monkeypatch,
                                               devices8):
    """The smoke reports which loader ran and must work with either:
    the python path (taken when g++ cannot build the C++ loader) yields
    the same vocabulary, token stream and read-back as the native one."""
    import pytest

    from swiftmpi_tpu.data import native

    if not native.available():
        pytest.skip("native loader not built: nothing to compare with")
    corpus = str(tmp_path / "c.txt")
    conf = str(tmp_path / "s.conf")
    chip_smoke.write_corpus(corpus, 300, 2000)
    chip_smoke.write_conf(conf, len_vec=8)
    vocab, tokens, offsets, loader = chip_smoke.load_corpus(corpus, 1)
    assert loader == "native"
    model = chip_smoke.build_model(conf, vocab)
    emb = str(tmp_path / "e.txt")
    model.save(emb)
    keys, rows = chip_smoke.read_embeddings(emb, 8)

    monkeypatch.setattr(native, "available", lambda: False)
    vocab_p, tokens_p, offsets_p, loader = chip_smoke.load_corpus(corpus, 1)
    assert loader == "python"
    np.testing.assert_array_equal(vocab_p.keys, vocab.keys)
    np.testing.assert_array_equal(tokens_p, tokens)
    np.testing.assert_array_equal(offsets_p, offsets)
    batch = next(chip_smoke.make_batcher(
        model, vocab_p, tokens_p, offsets_p, loader).epoch(64))
    assert len(batch.centers) == 64
    keys_p, rows_p = chip_smoke.read_embeddings(emb, 8)
    np.testing.assert_array_equal(keys_p, keys)
    np.testing.assert_allclose(rows_p, rows, rtol=1e-6)
