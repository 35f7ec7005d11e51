"""DMA ring push tests (ops/pallas_ring.py, interpret mode on the
8-device CPU mesh): ring_exchange parity vs ``lax.all_to_all`` on float
and int operands, the knob/mesh routing gate, and end-to-end TpuTransfer
push / push_span / push_window parity with the ring forced on — the
on-chip A/B lives in ``scripts/scatter_micro.py --ring-ab``.  A kernel
that cannot run here is a failure, not a skip.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from swiftmpi_tpu.cluster import SHARD_AXIS, ps_mesh  # noqa: E402
from swiftmpi_tpu.ops import calibration  # noqa: E402
from swiftmpi_tpu.ops.pallas_ring import (ring_exchange,  # noqa: E402
                                          use_ring_push)
from swiftmpi_tpu.parameter import KeyIndex, SparseTable  # noqa: E402
from swiftmpi_tpu.parameter import w2v_access  # noqa: E402
from swiftmpi_tpu.transfer.tpu import TpuTransfer  # noqa: E402


@pytest.fixture
def ring_mesh(devices8):
    return Mesh(np.asarray(devices8), ("x",))


def _wrap(mesh, f):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                                 out_specs=P("x"), check_vma=False))


def test_ring_exchange_matches_all_to_all(ring_mesh):
    """Block j of the ring result is the block received from device j —
    exactly ``all_to_all(x, axis, 0, 0, tiled=True)`` — for the float
    grad buckets and the int32 request-id buckets alike."""
    n = 8
    rng = np.random.default_rng(0)
    ring = _wrap(ring_mesh, lambda b: ring_exchange(b[0], "x", n)[None])
    a2a = _wrap(ring_mesh, lambda b: jax.lax.all_to_all(
        b[0], "x", 0, 0, tiled=True)[None])
    x = jnp.asarray(rng.standard_normal((n, n, 6, 9)), jnp.float32)
    np.testing.assert_allclose(np.asarray(ring(x)), np.asarray(a2a(x)),
                               rtol=1e-6)
    xi = jnp.asarray(rng.integers(0, 1000, (n, n, 16)), jnp.int32)
    np.testing.assert_array_equal(np.asarray(ring(xi)),
                                  np.asarray(a2a(xi)))


def test_ring_exchange_rejects_wrong_leading_dim(ring_mesh):
    bad = jnp.zeros((8, 4, 16), jnp.float32)    # block dim 4 != n=8
    with pytest.raises(ValueError, match="leading dim"):
        _wrap(ring_mesh, lambda b: ring_exchange(b[0], "x", 8)[None])(bad)


def test_use_ring_push_gate(monkeypatch, tmp_path):
    """Routing: a real exchange (n > 1) on a 1-D mesh is a precondition
    no override can lift (LOGICAL device ids equal axis indices only
    there); above that, env override beats the data_plane knob, and
    auto needs a measured on-chip win for this device kind."""
    monkeypatch.setenv("SMTPU_CALIBRATION", str(tmp_path / "c.json"))
    calibration.reset_cache()
    monkeypatch.delenv("SMTPU_RING_PUSH", raising=False)
    assert not use_ring_push(8, True, "auto")     # cpu, no verdict
    assert use_ring_push(8, True, "pallas")       # operator pin
    assert not use_ring_push(8, False, "pallas")  # hybrid 2-D mesh
    assert not use_ring_push(1, True, "pallas")   # nothing to exchange
    assert not use_ring_push(8, True, "xla")
    monkeypatch.setenv("SMTPU_RING_PUSH", "1")
    assert use_ring_push(8, True, "xla")          # env beats knob
    assert not use_ring_push(8, False, "xla")     # but never an unfit mesh
    monkeypatch.setenv("SMTPU_RING_PUSH", "0")
    assert not use_ring_push(8, True, "pallas")
    monkeypatch.delenv("SMTPU_RING_PUSH", raising=False)
    with pytest.raises(ValueError):
        use_ring_push(8, True, "bogus")
    monkeypatch.setattr(calibration, "on_tpu", lambda: True)
    monkeypatch.setattr(calibration, "device_key", lambda: "TPU v5 lite")
    calibration.record("ring_push", "TPU v5 lite",
                       {"win": True, "pallas_ms": 1.0, "xla_ms": 2.0})
    assert use_ring_push(8, True, "auto")
    monkeypatch.setattr(calibration, "device_key", lambda: "TPU v4")
    assert not use_ring_push(8, True, "auto")
    calibration.reset_cache()


# -- end-to-end: TpuTransfer with the ring forced on ----------------------


def _setup(devices8):
    mesh = ps_mesh()
    access = w2v_access(learning_rate=0.3, len_vec=8)
    ki = KeyIndex(num_shards=8, capacity_per_shard=32)
    table = SparseTable(access, ki, mesh=mesh, axis=SHARD_AXIS)
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 10_000, size=64).astype(np.uint64)
    slots = ki.lookup(keys)
    slots[::7] = -1
    grads = {f: rng.normal(size=(64, 8)).astype(np.float32)
             for f in access.grad_fields}
    return mesh, access, table, slots, grads


def _arm(monkeypatch, mesh, flag):
    # fresh transfer per arm: the push program cache is per-instance and
    # the ring/all_to_all choice is resolved at build time
    monkeypatch.setenv("SMTPU_RING_PUSH", flag)
    return TpuTransfer(mesh)


@pytest.mark.parametrize("mean", [False, True])
def test_tpu_push_ring_matches_all_to_all(monkeypatch, devices8, mean):
    """The full bucket push (request routing + grad buckets, both wire
    exchanges through the ring) must reproduce the all_to_all path's
    post-push state, duplicates and -1 padding included."""
    mesh, access, table, slots, grads = _setup(devices8)
    off = _arm(monkeypatch, mesh, "0").push(table.state, slots, grads,
                                            access, mean=mean)
    on = _arm(monkeypatch, mesh, "1").push(table.state, slots, grads,
                                           access, mean=mean)
    for f in access.fields:
        np.testing.assert_allclose(np.asarray(off[f]), np.asarray(on[f]),
                                   rtol=1e-6, atol=1e-7, err_msg=f)


def test_tpu_push_span_ring_matches_all_to_all(monkeypatch, devices8):
    """The stencil span push (synthetic counts field riding the bucket
    routing) through the ring."""
    mesh, access, table, slots, grads = _setup(devices8)
    counts = np.maximum(
        np.random.default_rng(2).integers(0, 4, size=64), 0
    ).astype(np.float32)
    off = _arm(monkeypatch, mesh, "0").push_span(
        table.state, slots, grads, counts, access, mean=True)
    on = _arm(monkeypatch, mesh, "1").push_span(
        table.state, slots, grads, counts, access, mean=True)
    for f in access.fields:
        np.testing.assert_allclose(np.asarray(off[f]), np.asarray(on[f]),
                                   rtol=1e-6, atol=1e-7, err_msg=f)


def test_tpu_push_window_ring_matches_all_to_all(monkeypatch, devices8):
    """The window-coalesced push's single exchange through the ring: a
    (W, B) window, sparse wire format (the one that routes through the
    bucket exchange the ring replaces)."""
    mesh, access, table, _, _ = _setup(devices8)
    rng = np.random.default_rng(3)
    W, B = 4, 32
    ki = table.key_index
    keys = rng.integers(0, 10_000, size=(W * B)).astype(np.uint64)
    slots = ki.lookup(keys).reshape(W, B)
    slots[:, ::9] = -1
    grads = {f: rng.normal(size=(W, B, 8)).astype(np.float32)
             for f in access.grad_fields}
    off = _arm(monkeypatch, mesh, "0").push_window(
        table.state, slots, grads, access)
    on = _arm(monkeypatch, mesh, "1").push_window(
        table.state, slots, grads, access)
    for f in access.fields:
        np.testing.assert_allclose(np.asarray(off[f]), np.asarray(on[f]),
                                   rtol=1e-6, atol=1e-7, err_msg=f)


@pytest.mark.slow
def test_ring_ab_cell_records_verdict(monkeypatch, devices8, tmp_path):
    """The `scatter_micro --ring-ab` cell end-to-end at reduced shape
    (the chip-session lane, excluded from tier-1): runs the A/B and
    records a stack-stamped verdict under the right device kind."""
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "scripts"))
    import scatter_micro

    monkeypatch.setenv("SMTPU_CALIBRATION", str(tmp_path / "c.json"))
    calibration.reset_cache()
    scatter_micro.ring_ab(C=64, width=9)
    kind = (calibration.device_key() if calibration.on_tpu()
            else calibration.INTERPRET_KIND)
    v = calibration.lookup("ring_push", kind)
    assert v is not None
    assert v["stack"] == calibration.stack_key()
    calibration.reset_cache()
