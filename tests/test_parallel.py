"""Context-parallel attention vs full-attention golden on an 8-device mesh."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import Mesh

from swiftmpi_tpu.parallel import (full_attention, psum, ring_attention,
                                   ring_permute, ulysses_attention)

# the package exports the function ``ring_attention`` under the module's name
ra = importlib.import_module("swiftmpi_tpu.parallel.ring_attention")


@pytest.fixture
def seq_mesh(devices8):
    return Mesh(np.asarray(devices8), ("seq",))


def qkv(B=2, S=64, H=8, D=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    return mk(), mk(), mk()


def test_ring_attention_matches_full(seq_mesh):
    q, k, v = qkv()
    got = ring_attention(q, k, v, seq_mesh)
    want = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_causal_matches_full(seq_mesh):
    q, k, v = qkv(seed=1)
    got = ring_attention(q, k, v, seq_mesh, causal=True)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_ulysses_attention_matches_full(seq_mesh):
    q, k, v = qkv(seed=2)
    got = ulysses_attention(q, k, v, seq_mesh)
    want = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_ulysses_attention_causal_matches_full(seq_mesh):
    q, k, v = qkv(seed=3)
    got = ulysses_attention(q, k, v, seq_mesh, causal=True)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_ulysses_rejects_indivisible_heads(seq_mesh):
    q, k, v = qkv(H=6)
    with pytest.raises(ValueError):
        ulysses_attention(q, k, v, seq_mesh)


def test_ring_attention_under_jit_and_long_seq(seq_mesh):
    # jit-wrapped, longer sequence, odd head dim
    q, k, v = qkv(B=1, S=128, H=4, D=8, seed=4)
    f = jax.jit(lambda a, b, c: ring_attention(a, b, c, seq_mesh,
                                               causal=True))
    got = f(q, k, v)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_collective_wrappers(seq_mesh):
    from jax.sharding import PartitionSpec as P
    x = jnp.arange(8.0)

    def body(x):
        return psum(x, "seq"), ring_permute(x, "seq")

    s, r = jax.shard_map(body, mesh=seq_mesh, in_specs=P("seq"),
                         out_specs=(P(), P("seq")))(x)
    assert float(s[0]) == 28.0
    # ring shift: block j moves to j+1
    np.testing.assert_array_equal(np.asarray(r),
                                  np.roll(np.arange(8.0), 1))


# -- blockwise attention's backward: query tiles in turn, as the forward -------

def _masked_attention(q, k, v, see):
    """``full_attention`` under the explicit ``(S, S)`` mask ``see`` with
    grouped KV heads: f32 scores and softmax whatever the inputs' dtype."""
    group = q.shape[2] // k.shape[2]
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _mask_and_matrix(kind, S):
    """A mask ``blockwise_attention`` takes and the boolean matrix it
    stands for, from the mask's own element predicate."""
    from swiftmpi_tpu.models.diffusion import BlockDiffusionMask
    mask = {"causal": ra.CAUSAL,
            "window19": ra.WindowMask(19),           # no multiple of a tile
            "window1": ra.WindowMask(1),
            "block_diffusion": BlockDiffusionMask(S // 2, 4)}[kind]
    pos = jnp.arange(S)
    return mask, mask.visible(pos[:, None], pos[None, :])


def _grads_agree(kind, G, tile, dtype, tol, S=64, Hkv=2, D=8):
    mask, see = _mask_and_matrix(kind, S)
    kq, kk, kv, kw = jax.random.split(jax.random.key(S + G + tile), 4)
    q = jax.random.normal(kq, (2, S, Hkv * G, D)).astype(dtype)
    k = jax.random.normal(kk, (2, S, Hkv, D)).astype(dtype)
    v = jax.random.normal(kv, (2, S, Hkv, D)).astype(dtype)
    w = jax.random.normal(kw, q.shape)

    def tiled(q, k, v):
        return ra.blockwise_attention(q, k, v, block=tile, mask=mask)

    got = jax.grad(lambda *a: (tiled(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_masked_attention(*a, see) * w).sum(),
                    (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert a.dtype == dtype and a.shape == b.shape
        a, b = (np.asarray(t, np.float64) for t in (a, b))
        # window 1: a query sees itself alone, so dq and dk are zero
        err = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1.0)
        assert err < tol, (name, err)


# bf16: the operands of the five products are rounded to 8 bits (2^-9 an
# element), the sums stay f32; no bf16 case of this routine existed before
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("tile", [8, 16])
@pytest.mark.parametrize("G", [1, 4, 8])
@pytest.mark.parametrize("kind", ["causal", "window19", "block_diffusion"])
def test_blockwise_backward_against_the_explicit_mask(kind, G, tile, dtype,
                                                      tol):
    """Gradients of ``blockwise_attention`` — ``dq`` summed a query tile
    at a time in the fold's carry, ``dk`` / ``dv`` a key tile a fold —
    against plain attention under the boolean mask."""
    _grads_agree(kind, G, tile, dtype, tol)


@pytest.mark.parametrize("kind,tile,los,folds", [
    ("causal", 64, [0], [1]),                    # one tile: one fold
    ("window1", 8, list(range(8)), [1] * 8),     # every list a single tile
    ("window19", 8, [0, 0, 0, 0, 1, 2, 3, 4], [1, 2, 3, 4, 4, 4, 4, 4]),
], ids=["one-tile", "single-tile-lists", "band-lo-above-0"])
def test_blockwise_backward_at_the_lists_edges(kind, tile, los, folds):
    """The fold that runs once, and the band whose list starts above tile
    0 (``lo > 0``: the carries' tiles below it are never touched)."""
    S = 64
    mask, _see = _mask_and_matrix(kind, S)
    lists = [mask.key_tiles(i, S // tile, tile) for i in range(S // tile)]
    assert [int(lo) for lo, _hi, _t in lists] == los
    assert [int(hi) - int(lo) for lo, hi, _t in lists] == folds
    _grads_agree(kind, 4, tile, jnp.float32, 1e-5, S=S)
