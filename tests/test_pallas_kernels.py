"""Pallas AdaGrad kernel vs the pure-jnp rule (interpret mode on CPU)."""

import numpy as np
import pytest

import jax.numpy as jnp

from swiftmpi_tpu.ops.pallas_kernels import adagrad_update
from swiftmpi_tpu.parameter.access import (AdaGradRule, FieldSpec,
                                           PallasAdaGradAccess, w2v_access,
                                           zeros_init)


@pytest.mark.parametrize("shape", [(64, 100), (1000, 100), (7, 3), (513,)])
def test_adagrad_kernel_matches_rule(shape):
    rng = np.random.default_rng(1)
    p = rng.normal(size=shape).astype(np.float32)
    a = np.abs(rng.normal(size=shape)).astype(np.float32)
    g = rng.normal(size=shape).astype(np.float32)
    a2 = a + g * g
    p2 = p + 0.7 * g / np.sqrt(a2 + 1e-6)
    po, ao = adagrad_update(jnp.asarray(p), jnp.asarray(a), jnp.asarray(g),
                            lr=0.7, interpret=True, block_rows=8)
    np.testing.assert_allclose(np.asarray(ao), a2, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(po), p2, rtol=1e-5, atol=1e-6)


def test_pallas_access_matches_base_access():
    base = w2v_access(0.3, 16)
    pallas = PallasAdaGradAccess(
        0.3, rules=base.rules, fields=base.fields,
        pull_fields=base.pull_fields)
    rng = np.random.default_rng(2)
    params = {f: rng.normal(size=(32, 16)).astype(np.float32)
              for f in base.fields}
    params["h2sum"] = np.abs(params["h2sum"])
    params["v2sum"] = np.abs(params["v2sum"])
    grads = {f: rng.normal(size=(32, 16)).astype(np.float32)
             for f in base.grad_fields}
    out_base = base.apply_push(params, grads)
    out_pallas = pallas.apply_push(params, grads)
    for f in base.fields:
        np.testing.assert_allclose(np.asarray(out_base[f]),
                                   np.asarray(out_pallas[f]),
                                   rtol=1e-5, atol=1e-6)


def test_multi_step_scan_matches_single_steps(devices8):
    import jax
    from swiftmpi_tpu.data.text import CBOWBatcher, synthetic_corpus
    from swiftmpi_tpu.models import Word2Vec
    from swiftmpi_tpu.utils import ConfigParser

    cfg = ConfigParser().update({
        "cluster": {"transfer": "xla"},
        "word2vec": {"len_vec": 8, "window": 2, "negative": 3,
                     "sample": -1, "learning_rate": 0.05},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 128},
    })
    corpus = synthetic_corpus(20, vocab_size=40, length=12, seed=9)
    model = Word2Vec(config=cfg)
    model.build(corpus)
    model.stencil = 0      # drives the per-pair builders itself
    batches = list(CBOWBatcher(corpus, model.vocab, 2).epoch(64))[:2]
    import jax.numpy as jnp
    centers = jnp.stack([jnp.asarray(b.centers) for b in batches])
    contexts = jnp.stack([jnp.asarray(b.contexts) for b in batches])
    masks = jnp.stack([jnp.asarray(b.ctx_mask) for b in batches])

    multi = model._build_multi_step(2)
    key = jax.random.key(7)
    # deep-copy: multi donates its state argument
    state_copy = {f: jnp.array(v) for f, v in model.table.state.items()}
    # the program splits the key it is given, then once a step
    sub = jax.random.split(key)[1]
    s_multi, *_sums = multi(
        state_copy, model._slot_of_vocab, model._alias_prob,
        model._alias_idx, centers, contexts, masks, key)

    grads_fn = jax.jit(model._build_grads())
    apply_fn = jax.jit(model._build_apply())
    s = dict(model.table.state)
    keys = jax.random.split(sub, 2)
    for i in range(2):
        pushes, _, _ = grads_fn(
            s, model._slot_of_vocab, model._alias_prob, model._alias_idx,
            centers[i], contexts[i], masks[i], keys[i])
        s = apply_fn(s, pushes)
    for f in s:
        np.testing.assert_allclose(np.asarray(s[f]),
                                   np.asarray(s_multi[f]),
                                   rtol=1e-5, atol=1e-6)


def test_vmem_gather_matches_take(devices8):
    """ops/pallas_gather.py: VMEM-resident gather == jnp.take (interpret
    mode on CPU; the on-chip A/B lives in scripts/gather_micro.py)."""
    from swiftmpi_tpu.ops.pallas_gather import fits_vmem, vmem_gather

    rng = np.random.default_rng(4)
    table = jnp.asarray(rng.standard_normal((777, 36)), jnp.float32)
    idx = jnp.asarray(rng.integers(-1, 777, 4096), jnp.int32)  # incl. -1
    got = vmem_gather(table, idx, idx_block=1024)
    want = jnp.take(table, jnp.clip(idx, 0, 776), axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    assert fits_vmem(table)
    assert not fits_vmem(jnp.zeros((1 << 20, 100), jnp.float32))
    with pytest.raises(ValueError):
        vmem_gather(table, idx[:1000], idx_block=1024)


def test_masked_vmem_gather_matches_masked_take(devices8):
    """masked_vmem_gather == the xla backend's masked gather semantics,
    including non-block-multiple lengths (padding) and invalid slots."""
    from swiftmpi_tpu.ops.pallas_gather import masked_vmem_gather
    from swiftmpi_tpu.transfer.xla import _masked_gather

    rng = np.random.default_rng(9)
    table = jnp.asarray(rng.standard_normal((513, 20)), jnp.float32)
    slots = jnp.asarray(rng.integers(-1, 513, 1000), jnp.int32)
    valid = slots >= 0
    got = masked_vmem_gather(table, slots, valid)
    want = _masked_gather(table, slots, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_use_vmem_gather_gate(monkeypatch, tmp_path):
    """The measurement-driven gate: off by default without a recorded
    chip win; env force-on/off overrides; oversized tables never route."""
    from swiftmpi_tpu.ops import calibration
    from swiftmpi_tpu.ops.pallas_gather import use_vmem_gather

    monkeypatch.setenv("SMTPU_CALIBRATION",
                       str(tmp_path / "calib.json"))
    calibration.reset_cache()
    small = jnp.zeros((1000, 50), jnp.float32)
    huge = jnp.zeros((1 << 20, 100), jnp.float32)

    monkeypatch.delenv("SMTPU_PALLAS_GATHER", raising=False)
    assert not use_vmem_gather(small)      # cpu backend, no verdict
    monkeypatch.setenv("SMTPU_PALLAS_GATHER", "1")
    assert use_vmem_gather(small)          # forced on (fits)
    assert not use_vmem_gather(huge)       # forced on but doesn't fit
    monkeypatch.setenv("SMTPU_PALLAS_GATHER", "0")
    assert not use_vmem_gather(small)      # forced off

    # recorded win flips auto mode on a single tpu device (simulated):
    # verdicts are keyed by device KIND so one generation's win never
    # gates another's kernel
    monkeypatch.delenv("SMTPU_PALLAS_GATHER", raising=False)
    import jax as _jax
    monkeypatch.setattr(calibration, "on_tpu", lambda: True)
    monkeypatch.setattr(_jax, "device_count", lambda: 1)
    monkeypatch.setattr(calibration, "device_key", lambda: "TPU v5 lite")
    calibration.record("vmem_gather", "TPU v5 lite",
                       {"win": True, "pallas_ms": 1.0, "xla_ms": 5.0})
    assert use_vmem_gather(small)
    # a different device kind has no verdict -> stays off
    monkeypatch.setattr(calibration, "device_key", lambda: "TPU v4")
    assert not use_vmem_gather(small)
    # multi-device (sharded-operand hazard) -> auto mode stays off
    monkeypatch.setattr(calibration, "device_key", lambda: "TPU v5 lite")
    monkeypatch.setattr(_jax, "device_count", lambda: 8)
    assert not use_vmem_gather(small)
    monkeypatch.setattr(_jax, "device_count", lambda: 1)
    calibration.record("vmem_gather", "TPU v5 lite", {"win": False})
    assert not use_vmem_gather(small)
    calibration.reset_cache()


def test_w2v_step_with_pallas_pull_matches_xla(monkeypatch, devices8):
    """End-to-end: the parity-mode w2v step with the VMEM gather forced
    on (interpret mode on CPU) produces the same loss as the XLA gather
    path — the wiring in transfer/xla.py preserves semantics exactly."""
    import jax
    from swiftmpi_tpu.cluster.cluster import Cluster
    from swiftmpi_tpu.data.text import CBOWBatcher, synthetic_corpus
    from swiftmpi_tpu.models.word2vec import Word2Vec
    from swiftmpi_tpu.utils import ConfigParser

    def run(force):
        if force:
            monkeypatch.setenv("SMTPU_PALLAS_GATHER", "1")
        else:
            monkeypatch.setenv("SMTPU_PALLAS_GATHER", "0")
        cfg = ConfigParser().update({
            "cluster": {"transfer": "xla", "server_num": 1},
            "word2vec": {"len_vec": 16, "window": 3, "negative": 4,
                         "sample": -1, "learning_rate": 0.05},
            "server": {"initial_learning_rate": 0.7, "frag_num": 100},
            "worker": {"minibatch": 50},
        })
        m = Word2Vec(config=cfg, cluster=Cluster(cfg).initialize())
        corpus = synthetic_corpus(20, 200, 40, seed=13)
        m.build(corpus)
        m.stencil = 0      # drives the per-pair builders itself
        step = jax.jit(m._build_step())
        batcher = CBOWBatcher(corpus, m.vocab, m.window, m.sample, seed=5)
        b = next(iter(batcher.epoch(128)))
        state = dict(m.table.state)
        state, _key, _tally, es = step(
            state, m._slot_of_vocab, m._alias_prob, m._alias_idx,
            jnp.asarray(b.centers), jnp.asarray(b.contexts),
            jnp.asarray(b.ctx_mask), jax.random.key(0))
        return float(es), {f: np.asarray(v) for f, v in state.items()}

    es0, st0 = run(False)
    es1, st1 = run(True)
    assert es0 == pytest.approx(es1, rel=1e-6)
    for f in st0:
        np.testing.assert_allclose(st1[f], st0[f], rtol=1e-6)


def test_vmem_scatter_add_matches_xla(devices8):
    """ops/pallas_scatter.py: VMEM-resident scatter-add == .at[].add
    with drop semantics (interpret mode; chip A/B in scatter_micro)."""
    from swiftmpi_tpu.ops.pallas_scatter import (fits_vmem,
                                                 vmem_scatter_add)

    rng = np.random.default_rng(5)
    cap, W, n = 97, 8, 512
    idx = jnp.asarray(rng.integers(0, cap + 1, n), jnp.int32)  # incl dump
    g = jnp.asarray(rng.standard_normal((n, W)), jnp.float32)
    got = vmem_scatter_add(idx, g, cap, idx_block=128)
    want = jnp.zeros((cap + 1, W), jnp.float32).at[idx].add(g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert fits_vmem(17_314, 101)
    assert not fits_vmem(1 << 20, 101)


def test_masked_vmem_scatter_matches_push_semantics(devices8):
    """masked wrapper: invalid slots dropped, non-block-multiple length
    padded, result shape (capacity, W)."""
    from swiftmpi_tpu.ops.pallas_scatter import masked_vmem_scatter_add

    rng = np.random.default_rng(6)
    cap, W, n = 61, 4, 300        # 300 pads up to 4096
    slots = jnp.asarray(rng.integers(-1, cap, n), jnp.int32)
    valid = slots >= 0
    g = jnp.asarray(rng.standard_normal((n, W)), jnp.float32)
    got = masked_vmem_scatter_add(slots, valid, g, cap)
    safe = jnp.where(valid, slots, cap)
    want = jnp.zeros((cap, W), jnp.float32).at[safe].add(g, mode="drop")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_w2v_step_with_pallas_scatter_matches_xla(monkeypatch, devices8):
    """End-to-end: parity-mode step with the VMEM scatter forced on
    (interpret) == the XLA scatter path."""
    import jax
    from swiftmpi_tpu.cluster.cluster import Cluster
    from swiftmpi_tpu.data.text import CBOWBatcher, synthetic_corpus
    from swiftmpi_tpu.models.word2vec import Word2Vec
    from swiftmpi_tpu.utils import ConfigParser

    def run(force):
        monkeypatch.setenv("SMTPU_PALLAS_SCATTER", "1" if force else "0")
        cfg = ConfigParser().update({
            "cluster": {"transfer": "xla", "server_num": 1},
            "word2vec": {"len_vec": 16, "window": 3, "negative": 4,
                         "sample": -1, "learning_rate": 0.05},
            "server": {"initial_learning_rate": 0.7, "frag_num": 100},
            "worker": {"minibatch": 50},
        })
        m = Word2Vec(config=cfg, cluster=Cluster(cfg).initialize())
        corpus = synthetic_corpus(10, 100, 30, seed=17)
        m.build(corpus)
        m.stencil = 0      # drives the per-pair builders itself
        step = jax.jit(m._build_step())
        batcher = CBOWBatcher(corpus, m.vocab, m.window, m.sample, seed=5)
        b = next(iter(batcher.epoch(64)))
        state = dict(m.table.state)
        state, _key, _tally, es = step(
            state, m._slot_of_vocab, m._alias_prob, m._alias_idx,
            jnp.asarray(b.centers), jnp.asarray(b.contexts),
            jnp.asarray(b.ctx_mask), jax.random.key(0))
        return float(es), {f: np.asarray(v) for f, v in state.items()}

    es0, st0 = run(False)
    es1, st1 = run(True)
    assert es0 == pytest.approx(es1, rel=1e-5)
    for f in st0:
        np.testing.assert_allclose(st1[f], st0[f], rtol=1e-5, atol=1e-6)


def test_calibration_clear_removes_only_named_kernel(monkeypatch,
                                                     tmp_path):
    """The rollback path: clearing one
    kernel's verdicts must not touch other kernels' entries."""
    from swiftmpi_tpu.ops import calibration

    monkeypatch.setenv("SMTPU_CALIBRATION", str(tmp_path / "c.json"))
    calibration.reset_cache()
    calibration.record("vmem_gather", "TPU v5 lite", {"win": True})
    calibration.record("vmem_gather", "TPU v4", {"win": True})
    calibration.record("vmem_scatter", "TPU v5 lite", {"win": True})
    calibration.clear("vmem_gather")
    assert calibration.lookup("vmem_gather", "TPU v5 lite") is None
    assert calibration.lookup("vmem_gather", "TPU v4") is None
    assert calibration.lookup("vmem_scatter", "TPU v5 lite")["win"]
    calibration.clear("nonexistent")          # no-op, no crash
    calibration.reset_cache()


def test_pallas_status_marker(monkeypatch, tmp_path):
    """r5 verdict Next #6: with no measured on-chip A/B verdict for a
    device key, pallas_status says `unvalidated-on-tpu` explicitly; a
    recorded lowering error is an attempt, not a validation; only a
    measured pallas_ms/xla_ms pair flips the status to validated."""
    from swiftmpi_tpu.ops import calibration

    monkeypatch.setenv("SMTPU_CALIBRATION", str(tmp_path / "calib.json"))
    calibration.reset_cache()
    assert calibration.pallas_status("TPU v5 lite") == "unvalidated-on-tpu"
    # a bare win flag without the measured A/B pair does not validate
    calibration.record("vmem_gather", "TPU v5 lite", {"win": True})
    assert calibration.pallas_status(
        "TPU v5 lite") == "unvalidated-on-tpu"
    # a lowering failure: attempted, named, still unvalidated
    calibration.record("vmem_scatter", "TPU v5 lite",
                       {"win": False, "error": "remote compile 500",
                        "xla_ms": 5.0})
    st = calibration.pallas_status("TPU v5 lite")
    assert st.startswith("unvalidated-on-tpu (attempted")
    assert "vmem_scatter" in st
    # a measured no-win A/B validates (the capability question has a
    # measured answer, even if the answer is "XLA rules")
    calibration.record("vmem_gather", "TPU v5 lite",
                       {"win": False, "pallas_ms": 6.0, "xla_ms": 5.0})
    assert calibration.pallas_status("TPU v5 lite") == "validated: no-win"
    # a measured win names the winning kernel
    calibration.record("replica_scatter", "TPU v5 lite",
                       {"win": True, "pallas_ms": 1.0, "xla_ms": 5.0})
    assert calibration.pallas_status(
        "TPU v5 lite") == "validated: win (replica_scatter)"
    # other device kinds stay independently unvalidated
    assert calibration.pallas_status("TPU v4") == "unvalidated-on-tpu"
    calibration.reset_cache()


def test_interpret_exercise_upgrades_marker(monkeypatch, tmp_path):
    """An interpret-mode numpy-oracle pass recorded via
    record_interpret distinguishes "never exercised" from "exercised
    off-chip": the unvalidated-on-tpu marker stays (no chip was
    involved) but names the kernels whose semantics a host oracle has
    confirmed, and the gate itself must never consult the interpret
    pseudo-kind."""
    from swiftmpi_tpu.ops import calibration
    from swiftmpi_tpu.ops.pallas_scatter import masked_vmem_scatter_add

    monkeypatch.setenv("SMTPU_CALIBRATION", str(tmp_path / "calib.json"))
    calibration.reset_cache()
    assert calibration.pallas_status("TPU v5 lite") == "unvalidated-on-tpu"

    # the actual off-chip exercise: interpret-mode kernel vs numpy oracle
    rng = np.random.default_rng(23)
    cap, W, n = 53, 4, 200
    slots = rng.integers(-1, cap, n).astype(np.int32)
    valid = slots >= 0
    g = rng.standard_normal((n, W)).astype(np.float32)
    got = np.asarray(masked_vmem_scatter_add(
        jnp.asarray(slots), jnp.asarray(valid), jnp.asarray(g), cap))
    want = np.zeros((cap, W), np.float32)
    np.add.at(want, slots[valid], g[valid])
    correct = np.allclose(got, want, rtol=1e-5, atol=1e-5)
    assert correct
    calibration.record_interpret("vmem_scatter", correct,
                                 shape=f"cap={cap} n={n} W={W}")

    st = calibration.pallas_status("TPU v5 lite")
    assert st.startswith("unvalidated-on-tpu (exercised off-chip")
    assert "vmem_scatter" in st
    # the recorded exercise is visible under the interpret pseudo-kind...
    v = calibration.lookup("vmem_scatter", calibration.INTERPRET_KIND)
    assert v["correct"] and v["interpret"]
    # ...but cannot arm the measurement gate for any real device kind
    monkeypatch.setenv("SMTPU_PALLAS_SCATTER", "auto")
    assert not calibration.gated("vmem_scatter", "SMTPU_PALLAS_SCATTER",
                                 fits=True, manual=True)
    # an on-chip measured A/B still wins over the off-chip marker
    calibration.record("vmem_scatter", "TPU v5 lite",
                       {"win": True, "pallas_ms": 1.0, "xla_ms": 5.0})
    assert calibration.pallas_status(
        "TPU v5 lite") == "validated: win (vmem_scatter)"
    calibration.reset_cache()


def test_calibration_stack_stamp_and_staleness(monkeypatch, tmp_path,
                                               capsys):
    """Verdict identity includes the software stack: record() stamps
    jaxlib/libtpu, and lookup() rejects — loudly, once per key — any
    verdict recorded without a stamp or under a different stack, while
    a current-stack verdict keeps resolving."""
    import json

    from swiftmpi_tpu.ops import calibration

    path = tmp_path / "c.json"
    monkeypatch.setenv("SMTPU_CALIBRATION", str(path))
    calibration.reset_cache()

    # record() stamps the current stack into the persisted verdict
    calibration.record("ring_push", "TPU v5 lite",
                       {"win": True, "pallas_ms": 1.0, "xla_ms": 2.0})
    raw = json.loads(path.read_text())
    assert raw["ring_push:TPU v5 lite"]["stack"] == calibration.stack_key()

    # externally-written file: one pre-stamp entry, one foreign-stack
    # entry, one current-stack entry
    raw["vmem_scatter:TPU v4"] = {
        "win": True, "pallas_ms": 1.0, "xla_ms": 2.0}
    raw["vmem_gather:TPU v4"] = {
        "win": True, "pallas_ms": 1.0, "xla_ms": 2.0,
        "stack": {"jaxlib": "0.0.1", "libtpu": "none"}}
    path.write_text(json.dumps(raw))
    calibration.reset_cache()

    assert calibration.lookup("vmem_scatter", "TPU v4") is None
    err = capsys.readouterr().err
    assert "RE-CALIBRATE" in err and "vmem_scatter:TPU v4" in err
    assert "pre-stamp" in err
    # the warning fires once per key, not per lookup
    assert calibration.lookup("vmem_scatter", "TPU v4") is None
    assert "RE-CALIBRATE" not in capsys.readouterr().err

    assert calibration.lookup("vmem_gather", "TPU v4") is None
    err = capsys.readouterr().err
    assert "RE-CALIBRATE" in err and "different stack" in err
    assert "jaxlib 0.0.1" in err

    # the current-stack verdict still steers gates
    assert calibration.lookup("ring_push", "TPU v5 lite")["win"]

    stale = dict(calibration.stale_keys())
    assert set(stale) == {"vmem_scatter:TPU v4", "vmem_gather:TPU v4"}
    calibration.reset_cache()


def test_calibration_stale_check_cli(monkeypatch, tmp_path, capsys):
    """`python -m swiftmpi_tpu.ops.calibration --stale-check` is the
    run_tier1.sh advisory: exit 0 always, ADVISORY text only when some
    verdict is stale on this stack."""
    import json

    from swiftmpi_tpu.ops import calibration

    path = tmp_path / "c.json"
    monkeypatch.setenv("SMTPU_CALIBRATION", str(path))
    calibration.reset_cache()

    assert calibration.main(["--stale-check"]) == 0
    assert "no verdict file" in capsys.readouterr().out

    calibration.record("ring_push", "TPU v5 lite",
                       {"win": True, "pallas_ms": 1.0, "xla_ms": 2.0})
    calibration.reset_cache()
    assert calibration.main(["--stale-check"]) == 0
    assert "match the current stack" in capsys.readouterr().out

    raw = json.loads(path.read_text())
    raw["vmem_scatter:TPU v4"] = {"win": True}
    path.write_text(json.dumps(raw))
    calibration.reset_cache()
    assert calibration.main(["--stale-check"]) == 0
    out = capsys.readouterr().out
    assert "ADVISORY" in out and "1/2" in out
    assert "vmem_scatter:TPU v4" in out
    calibration.reset_cache()
