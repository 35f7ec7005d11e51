"""The expert walk's last chunk (``parallel/moe.py::_walk``): whole chunks of
``row_chunk`` rows, then the rows that are left at the smallest rung of
``moe._rungs`` that holds them — at every edge of the ladder the layer and
its hand-written backward equal the dense reference, nothing is dropped and
``MoEStats.walked`` counts the rows computed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from swiftmpi_tpu.models.trainer import _expert_counters, _loss_parts
from swiftmpi_tpu.parallel import moe

T, K, D, F, E, HELD, ROWS = 24, 2, 16, 24, 8, 4, 16     # 48 picks, 3 chunks
RUNGS = (8, 16)


def _forced(picks: int, gated: bool):
    """A share of ``HELD`` experts and tokens of which exactly ``picks``
    picks land on it: token ``t`` flags ``picks // T`` (+ 1 for the first
    ``picks % T``) held experts — feature ``1 + j`` weighs on held expert
    ``j``, feature 0 (= 1) against all of them — and fills its ``K`` with
    experts that are not held.  The router reads those features alone."""
    p = moe.init_moe_params(jax.random.key(picks), D, F, E, held=HELD,
                            gated=gated)
    per = np.full(T, picks // T) + (np.arange(T) < picks % T)
    flags = np.zeros((T, HELD), np.float32)
    for i in range(int(per.max())):
        rows = np.nonzero(per > i)[0]
        flags[rows, (rows + i) % HELD] = 1.0
    x = jax.random.normal(jax.random.key(picks + 1), (T, D))
    x = x.at[:, 0].set(1.0).at[:, 1:1 + HELD].set(flags)
    router = jnp.zeros((D, E)).at[0, :HELD].set(-4.0)
    router = router.at[1 + np.arange(HELD), np.arange(HELD)].set(12.0)
    return p._replace(router=router), x


def _walked(picks: int) -> int:
    full, left = divmod(picks, ROWS)
    return full * ROWS + (min(r for r in RUNGS if r >= left) if left else 0)


def test_the_ladder_is_made_from_the_chunk_alone():
    assert moe._rungs(ROWS) == RUNGS
    assert moe._rungs(moe.ROW_CHUNK) == (4096, 8192)
    assert moe._rungs(5) == (5,)             # cannot be halved: one rung


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
@pytest.mark.parametrize("picks", [0, 1, 7, 8, 9, 15, 16, 17, 24, 40], ids=[
    "none", "one", "rung-1", "rung", "rung+1", "chunk-1", "chunk", "chunk+1",
    "chunk+rung", "2-chunks+rung"])
def test_layer_and_gradients_at_the_ladders_edges(picks, gated):
    p, x = _forced(picks, gated)
    act = "relu" if gated else "relu2"
    w = jax.random.normal(jax.random.key(7), (T, D))

    def walk(p, x):
        y, _aux, st = moe.expert_layer(p, x, k=K, held=(0, HELD), act=act,
                                       row_chunk=ROWS)
        return (y * w).sum(), (y, st)

    def dense(p, x):
        y, _aux = moe.moe_ffn_reference(p, x, k=K, held=(0, HELD), act=act)
        return (y * w).sum(), y

    (_, (y, st)), (gp, gx) = jax.value_and_grad(
        walk, argnums=(0, 1), has_aux=True)(p, x)
    (_, want), (rp, rx) = jax.value_and_grad(
        dense, argnums=(0, 1), has_aux=True)(p, x)
    assert float(st.held) == picks and float(st.dropped) == 0.0
    assert float(st.walked) == _walked(picks)
    close = dict(rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(y, want, **close)
    for name in ("w_in", "w_out") + (("w_gate",) if gated else ()):
        np.testing.assert_allclose(getattr(gp, name), getattr(rp, name),
                                   err_msg=name, **close)
    # a share cuts the router's path to the tokens on purpose
    # (`expert_layer`'s stop_gradient); the features the router does not
    # read have no other
    np.testing.assert_allclose(gx[:, 1 + HELD:], rx[:, 1 + HELD:], **close)


def test_moe_ffn_over_the_mesh_walks_the_same_ladder(devices8):
    """Two devices, 32 picks each way: a device's 64 received rows hold its
    ~32 picks, walked as two chunks of 16 and a rung."""
    mesh = Mesh(np.array(devices8[:2]), (moe.EXPERT_AXIS,))
    p = moe.init_moe_params(jax.random.key(0), 8, 16, 4, gated=True)
    x = jax.random.normal(jax.random.key(1), (32, 8))
    w = jax.random.normal(jax.random.key(2), (32, 8))
    got, g = jax.value_and_grad(lambda p, x: (moe.moe_ffn(
        p, x, mesh, k=2, row_chunk=16)[0] * w).sum(), argnums=(0, 1))(p, x)
    want, r = jax.value_and_grad(lambda p, x: (moe.moe_ffn_reference(
        p, x, k=2)[0] * w).sum(), argnums=(0, 1))(p, x)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(r)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def _stats(held, walked, layers=2.0):
    f32 = jnp.float32
    return moe.MoEStats(picks=f32(96.0), held=f32(held), dropped=f32(0.0),
                        walked=f32(walked), load_max_over_mean=f32(3.0),
                        layers=f32(layers))


@pytest.mark.parametrize("prefix", ["", "mtp_"])
def test_walk_fill_share_is_held_over_walked_across_steps(prefix):
    steps = [_stats(10.0, 16.0), _stats(30.0, 48.0)]
    if prefix:
        m = _loss_parts([{"main_loss": 1.0, "mtp_loss": 1.0, "mtp_stats": s}
                         for s in steps], 0.3)
    else:
        m = _expert_counters(steps)
    assert m[prefix + "walk_fill_share"] == pytest.approx(100 * 40 / 64)
    assert m[prefix + "held_pick_share"] == pytest.approx(100 * 40 / 192)
