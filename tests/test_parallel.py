"""Context-parallel attention vs full-attention golden on an 8-device mesh."""

import dataclasses
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


# -- the forward walk's kernel (parallel/attention_kernel.py), interpreted -----

ak = importlib.import_module("swiftmpi_tpu.parallel.attention_kernel")


@dataclasses.dataclass(frozen=True)
class _SkipsTile(ra.CausalMask):
    """Causal, but nobody sees the keys of tile ``skip``: the tile is in no
    list (a list that is not a range: ``tile(t)`` jumps over it)."""
    size: int
    skip: int = 1

    def key_tiles(self, i, n, size):
        assert size == self.size
        hi = jnp.where(i >= self.skip, jnp.maximum(i, 1), i + 1)
        return 0, hi, lambda t: jnp.where(t >= self.skip, t + 1, t)

    def visible(self, qa, kc):
        lo = self.skip * self.size
        return (qa >= kc) & ((kc < lo) | (kc >= lo + self.size))


def _kernel_case(kind, S, tile, B=1):
    """(mask, its data or None, the boolean matrix (B, S, S) it stands
    for)."""
    from swiftmpi_tpu.parallel import sparse_attention as sa
    if kind == "selected":
        # a random selection of the earlier keys; query 3 keeps one key
        keep = jax.random.bernoulli(jax.random.key(S), 0.3, (B, S, S))
        keep = (keep | jnp.eye(S, dtype=bool)) & jnp.tril(
            jnp.ones((S, S), bool))
        keep = keep.at[:, 3].set(jnp.arange(S) == 1)
        g = sa._group(tile)
        bits = (keep.reshape(B, S // g, g, S).astype(jnp.uint32)
                << sa._shifts(g)).sum(axis=2, dtype=jnp.uint32)
        assert bool((sa.unpack(bits, tile) == keep).all())
        return sa.SELECTED, bits, keep
    if kind == "skips_tile":
        mask = _SkipsTile(tile)
        pos = jnp.arange(S)
        see = mask.visible(pos[:, None], pos[None, :])
    else:
        mask, see = _mask_and_matrix(kind, S)
    return mask, None, jnp.broadcast_to(see, (B, S, S))


def _kernel_inputs(G, D, dtype, S, B=1):
    Hkv = 2 if D < 128 else 1           # two 64-wide heads fill a block
    kq, kk, kv = jax.random.split(jax.random.key(G + D + S), 3)
    return (jax.random.normal(kq, (B, S, Hkv, G, D)).astype(dtype),
            jax.random.normal(kk, (B, S, Hkv, D)).astype(dtype),
            jax.random.normal(kv, (B, S, Hkv, D)).astype(dtype))


def _interpreted(q, k, v, data, tile, mask):
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        return jax.jit(lambda q, k, v, data: ak.attn_fwd_tiles(
            q, k, v, data, tile, mask))(q, k, v, data)


@pytest.mark.parametrize("kind,G,D,dtype", [
    ("causal", 1, 64, jnp.float32), ("causal", 4, 128, jnp.bfloat16),
    ("causal", 16, 256, jnp.float32),
    ("window19", 4, 64, jnp.bfloat16), ("window19", 16, 128, jnp.float32),
    ("window19", 1, 256, jnp.bfloat16),
    ("block_diffusion", 4, 64, jnp.float32),
    ("block_diffusion", 1, 128, jnp.bfloat16),
    ("block_diffusion", 4, 256, jnp.float32),
    ("selected", 4, 64, jnp.float32), ("selected", 1, 128, jnp.bfloat16),
    ("selected", 16, 256, jnp.float32),
], ids=lambda x: getattr(x, "__name__", str(x)))
def test_forward_kernel_against_the_xla_walk_and_the_explicit_mask(
        kind, G, D, dtype):
    """``attn_fwd_tiles`` under Pallas' interpreter: ``o`` and ``lse`` are
    the XLA walk's (the same products in the same precision: f32 to
    rounding, bf16 to a step of the output) and plain attention's under
    the mask written out."""
    S, tile = 32, 8
    mask, data, see = _kernel_case(kind, S, tile)
    q, k, v = _kernel_inputs(G, D, dtype, S)
    o, lse = _interpreted(q, k, v, data, tile, mask)
    o_x, lse_x = ra._blockwise_fwd(q, k, v, data, tile, mask)
    assert o.dtype == dtype and o.shape == q.shape
    assert lse.dtype == jnp.float32 and lse.shape == q.shape[:-1]
    tol = 2e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_x, np.float32), atol=tol)
    np.testing.assert_allclose(lse, lse_x, atol=2e-5, rtol=1e-6)
    B, _S, Hkv, _G, _D = q.shape
    want = _masked_attention(q.reshape(B, S, Hkv * G, D), k, v, see[:, None])
    np.testing.assert_allclose(
        np.asarray(o, np.float32).reshape(want.shape), want,
        atol=1e-5 if dtype == jnp.float32 else 2e-2)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(D)
    want_lse = jax.nn.logsumexp(jnp.where(see[:, None, None], s, -jnp.inf),
                                axis=-1)
    np.testing.assert_allclose(lse, jnp.einsum("bhgq->bqhg", want_lse),
                               atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("kind", ["causal", "window19", "block_diffusion",
                                  "selected"])
def test_gradients_through_the_kernel_are_the_xla_walk_s(kind, monkeypatch):
    """The hand-written backward takes the kernel's residuals as it takes
    the XLA walk's: gradients of a loss on ``o`` agree."""
    S, tile, G, D = 32, 8, 4, 64
    mask, data, _see = _kernel_case(kind, S, tile)
    q, k, v = _kernel_inputs(G, D, jnp.float32, S)
    w = jax.random.normal(jax.random.key(7), q.shape)

    def grads():
        return jax.grad(lambda q, k, v: (ra._blockwise(
            q, k, v, data, tile, mask)[0] * w).sum(), (0, 1, 2))(q, k, v)

    want = grads()
    monkeypatch.setattr(ra, "_forward", lambda q, k, v, data, size, mask:
                        _interpreted(q, k, v, data, size, mask))
    for a, b in zip(grads(), want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("walk", ["xla", "tiles"])
def test_a_tile_in_no_list_is_never_multiplied(walk):
    """A key tile of NaNs that no list names changes nothing, in either
    walk: a product with it would poison every query that took it."""
    S, tile, G, D = 32, 8, 4, 64
    mask, data, _see = _kernel_case("skips_tile", S, tile)
    pairs = ak.tile_pairs(mask, S // tile, tile)
    assert 1 not in pairs[1] and sorted(set(pairs[0])) == [0, 1, 2, 3]
    q, k, v = _kernel_inputs(G, D, jnp.float32, S)
    poison = jnp.zeros(S).at[tile:2 * tile].set(jnp.nan)[None, :, None, None]
    fwd = _interpreted if walk == "tiles" else ra._blockwise_fwd
    o, lse = fwd(q, k, v, data, tile, mask)
    o_nan, lse_nan = fwd(q, k + poison, v + poison, data, tile, mask)
    assert bool(jnp.isfinite(o_nan).all() & jnp.isfinite(lse_nan).all())
    np.testing.assert_array_equal(o, o_nan)
    np.testing.assert_array_equal(lse, lse_nan)


@pytest.mark.parametrize("kind,S,tile,pairs", [
    ("causal", 64, 8, 36),             # the diagonal and everything under it
    ("window19", 64, 8, 26),           # a band: 1, 2, 3, 4, 4, 4, 4, 4 tiles
    ("window1", 64, 8, 8),
    ("block_diffusion", 64, 8, 24),    # N^2 + 2N of the 4 N^2, N = 4
    ("skips_tile", 32, 8, 7),
    ("selected", 64, 32, 3),
])
def test_tile_pairs_are_the_mask_s_lists_in_order(kind, S, tile, pairs):
    """``tile_pairs`` flattens ``mask.key_tiles``: every query tile's list
    whole, together and in its own order, opened and closed once."""
    mask, _data, _see = _kernel_case(kind, S, tile)
    n = S // tile
    q_of, k_of, flags = ak.tile_pairs(mask, n, tile)
    assert len(q_of) == pairs
    at = 0
    for i in range(n):
        lo, hi, tile_of = mask.key_tiles(i, n, tile)
        want = [int(tile_of(t)) for t in range(int(lo), int(hi))]
        assert list(k_of[at:at + len(want)]) == want
        assert list(q_of[at:at + len(want)]) == [i] * len(want)
        assert flags[at] & ak.FIRST and flags[at + len(want) - 1] & ak.LAST
        assert not any(flags[at + 1:at + len(want)] & ak.FIRST)
        assert not any(flags[at:at + len(want) - 1] & ak.LAST)
        at += len(want)
    assert at == len(q_of)
