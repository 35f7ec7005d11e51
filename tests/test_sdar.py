"""SDAR's block stack, trained by block diffusion, against the plain
reference (benchmark/reference/bdlm.py).

Seeded random weights at toy widths that keep every ratio of
``sdar-30b-a3b-ep8``: 8 query heads a KV head, ``d_head`` 16 over a 64-wide
residual (8 x 16 = 128, not 64), top-8 of 128 experts with 16 held (ids
112-127), an untied head, diffusion block 4.  float32 operands, so program
and reference agree to rounding.
"""

import dataclasses
import importlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.families import bdlm as family        # noqa: E402
from benchmark.lib import spec                       # noqa: E402
from benchmark.reference import bdlm as reference    # noqa: E402
from swiftmpi_tpu.models import diffusion            # noqa: E402
from swiftmpi_tpu.models import transformer as tfm   # noqa: E402
from swiftmpi_tpu.obs import costs                   # noqa: E402
from swiftmpi_tpu.parallel import moe                # noqa: E402

# (the package re-exports the function ring_attention under the module's name)
ra = importlib.import_module("swiftmpi_tpu.parallel.ring_attention")

CELL = "sdar-ep8-8k-t16k"
B, S = 2, 32


@pytest.fixture(scope="module")
def model():
    cell = spec.load_cell(CELL, rehearse=True)
    config = dict(cell.config, num_hidden_layers=2)
    traffic = dict(cell.traffic, sentence_tokens=S)
    cfg = dataclasses.replace(family.transformer_config(config, traffic),
                              remat=False)
    assert cfg.layer_groups() == [(("attention", "moe"), 2)]
    assert cfg.n_heads // cfg.kv_heads == 8
    assert cfg.head_dim == 16 != cfg.d_model // cfg.n_heads
    assert (cfg.n_experts, cfg.moe_top_k, cfg.held) == (128, 8, (112, 128))
    assert (cfg.objective, cfg.diffusion_block) == ("block_diffusion", 4)
    assert not cfg.tied_head and cfg.mask_token == cfg.vocab_size - 1
    params = tfm.init_params(jax.random.key(5), cfg)
    tokens = jax.random.randint(jax.random.key(6), (B, S), 0,
                                cfg.vocab_size - 1)
    m = reference.dims(config)
    return cfg, params, tokens, m, reference.Reference(m)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
    assert err < tol, err


# -- the layer ----------------------------------------------------------------

def test_layer_forward_and_gradient_with_wide_heads(model):
    """One layer on ``[x_t ; x_0]``: 8 heads of 16 over a 64-wide residual
    (``wq`` 64 x 128, ``wo`` 128 x 64), the block-diffusion mask, repeated
    position ids, softmax top-8 routing over a share."""
    cfg, params, _tokens, m, ref = model
    blk = reference.layers(params, m)[0][1]
    assert blk["wq"].shape == (64, 128) and blk["wo"].shape == (128, 64)
    assert blk["wk"].shape == (64, 16)
    x = jax.random.normal(jax.random.key(7), (B, 2 * S, cfg.d_model))
    w = jax.random.normal(jax.random.key(8), x.shape)
    mask = ref.mask(S)

    def prog(blk, x):
        return tfm.block_apply(blk, x, cfg, kind=("attention", "moe"),
                               **diffusion.attention_inputs(S, cfg))[0]

    def plain(blk, x):
        with reference.highest():
            return jnp.stack([reference.layer(blk, xb, mask, m) for xb in x])

    _close(prog(blk, x), plain(blk, x))
    gp = jax.grad(lambda b, a: (prog(b, a) * w).sum(), (0, 1))(blk, x)
    gr = jax.grad(lambda b, a: (plain(b, a) * w).sum(), (0, 1))(blk, x)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        _close(a, b, 1e-4)


@pytest.mark.parametrize("remat,chunk", [(False, 0), (True, 16)],
                         ids=["plain", "remat+chunked-loss"])
def test_whole_loss_and_gradient(model, remat, chunk):
    cfg, params, tokens, m, ref = model
    cfg = dataclasses.replace(cfg, remat=remat, remat_policy="full",
                              loss_chunk=chunk)
    key = jax.random.key(3)
    loss, grads = jax.value_and_grad(tfm.lm_loss)(
        params, tokens, cfg, aux_weight=0.0, noise_key=key)
    noisy, weights = diffusion.block_noise(key, tokens, cfg)
    want, gref = ref.loss_and_grads(params, tokens, noisy, weights)
    assert abs(float(loss) - want) < 1e-5 * want
    assert abs(ref.loss(params, np.asarray(tokens), noisy, weights)
               - want) < 1e-6 * want
    assert set(grads) == set(gref) == {"embed", "head", "blocks", "ln_f"}
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(gref)):
        _close(a, b, 2e-4)
    # the loss reads the noised half alone, but the clean copy's text
    # reaches it through attention: both vocabulary matrices get a gradient
    assert float(jnp.abs(grads["embed"]).max()) > 0
    assert float(jnp.abs(grads["head"]).max()) > 0


def test_block_diffusion_needs_its_key_and_its_attention(model):
    cfg, params, tokens, _m, _ref = model
    with pytest.raises(ValueError, match="needs a noise_key"):
        tfm.lm_loss(params, tokens, cfg)
    with pytest.raises(ValueError, match="needs attention 'blockwise'"):
        dataclasses.replace(cfg, attention="full")
    with pytest.raises(ValueError, match="unknown objective 'denoise'"):
        dataclasses.replace(cfg, objective="denoise")
    with pytest.raises(ValueError, match="objective 'next_token' only"):
        tfm.forward_pipelined(params, tokens, cfg, mesh=None)


def test_the_layers_do_not_know_the_objective(model):
    """``forward`` on plain tokens is a causal pass whatever ``objective``
    says (a sampler's or an evaluation's call); the loss lays out ``[x_t ;
    x_0]`` and hands positions and mask down; a mask needs the blockwise
    routine."""
    cfg, params, tokens, _m, _ref = model
    causal = dataclasses.replace(cfg, objective="next_token")
    np.testing.assert_array_equal(
        np.asarray(tfm.forward(params, tokens, cfg)[0]),
        np.asarray(tfm.forward(params, tokens, causal)[0]))
    attn = diffusion.attention_inputs(S, cfg)
    assert attn["mask"] == diffusion.BlockDiffusionMask(S, 4)
    assert attn["mask"].tile(512, 2 * S) == S and ra.CAUSAL.tile(512, S) == S
    z = diffusion.trunk_input(tokens, tokens)
    masked = tfm.trunk(params, z, cfg, **attn)[0]
    assert not np.allclose(np.asarray(masked),
                           np.asarray(tfm.trunk(params, z, cfg)[0]))
    with pytest.raises(ValueError, match="a mask needs 'blockwise'"):
        tfm.trunk(params, z, dataclasses.replace(causal, attention="full"),
                  **attn)


# -- the mask-structured blockwise attention ----------------------------------

def _qkvw(seq, H=8, Hkv=1, D=16, Bq=2):
    return [jax.random.normal(jax.random.key(i), shape)
            for i, shape in enumerate(
                [(Bq, seq, H, D), (Bq, seq, Hkv, D), (Bq, seq, Hkv, D),
                 (Bq, seq, H, D)])]


def _dense_attention(q, k, v, mask):
    """Plain softmax attention under a boolean (P, P) mask."""
    H, Hkv = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(t, H // Hkv, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("block", [32, 8, 4],
                         ids=["one-tile-a-half", "4-tiles", "8-tiles"])
def test_block_diffusion_attention_equals_dense_mask(block):
    q, k, v, w = _qkvw(2 * S)
    see = jnp.asarray(reference.visible_matrix(S, 4))
    mask = diffusion.BlockDiffusionMask(S, 4)

    def tiled(q, k, v):
        return ra.blockwise_attention(q, k, v, block=block, mask=mask)

    _close(tiled(q, k, v), _dense_attention(q, k, v, see), 1e-5)
    got = jax.grad(lambda *a: (tiled(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_dense_attention(*a, see) * w).sum(),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        _close(a, b, 1e-5)


@pytest.mark.parametrize("size", [4, 8, 32])
def test_mask_tile_lists_are_each_others_transpose_and_cover(size):
    """The key tiles of every query tile (the one list the forward and,
    since PR 39, the backward walk) name N^2 + 2N pairs, each once, and
    those hold every visible pair."""
    mask = diffusion.BlockDiffusionMask(S, 4)
    n, N = 2 * S // size, S // size
    see = reference.visible_matrix(S, 4)
    np.testing.assert_array_equal(
        np.asarray(diffusion.visible(np.arange(2 * S)[:, None],
                                     np.arange(2 * S)[None], S, 4)), see)
    assert see.sum() == S * S + S * 4

    fwd = []
    for i in range(n):
        lo, hi, tile = mask.key_tiles(i, n, size)
        fwd += [(i, int(tile(t))) for t in range(int(lo), int(hi))]
    assert len(fwd) == len(set(fwd)) == N * N + 2 * N
    covered = np.zeros_like(see)
    for i, j in fwd:
        covered[i * size:(i + 1) * size, j * size:(j + 1) * size] = True
    assert not (see & ~covered).any()
    with pytest.raises(ValueError, match="not two halves"):
        mask.key_tiles(0, n + 1, size)


def _causal_as_it_was(q, k, v, size):
    """``_blockwise_fwd`` of the tree before a mask could be given: query
    block ``i`` folds key blocks ``0..i``, future positions by ``>=``."""
    Bq, seq, Hkv, G, D = q.shape
    scale = 1.0 / math.sqrt(D)

    def q_block(i):
        qi = ra._block(q, i, size)

        def fold(j, carry):
            m, l, o = carry
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, ra._block(k, j, size),
                           preferred_element_type=jnp.float32) * scale
            pos = jnp.arange(size)
            s = jnp.where((i * size + pos)[:, None]
                          >= (j * size + pos)[None, :], s, ra._NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype),
                            ra._block(v, j, size),
                            preferred_element_type=jnp.float32)
            return (m_new, l * corr + p.sum(axis=-1),
                    o * corr[..., None] + pv)

        m0 = jnp.full((Bq, Hkv, G, size), ra._NEG, jnp.float32)
        m, l, o = lax.fori_loop(
            0, i + 1, fold, (m0, jnp.zeros_like(m0),
                             jnp.zeros((Bq, Hkv, G, size, D), jnp.float32)))
        return jnp.einsum("bhgqd->bqhgd", (o / l[..., None]).astype(q.dtype))

    o = lax.map(q_block, jnp.arange(seq // size))
    return jnp.moveaxis(o, 0, 1).reshape(q.shape)


def test_causal_case_is_what_it_was():
    """No mask given = ``CAUSAL``: the forward pass bit-equal to the loop as
    it stood, the gradients bit-equal between the two spellings and equal
    to the golden's."""
    q, k, v, w = _qkvw(S, H=8, Hkv=2)
    out = ra.blockwise_attention(q, k, v, block=8)
    was = _causal_as_it_was(q.reshape(2, S, 2, 4, 16), k, v, 8)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(was.reshape(out.shape)))

    def grads(**kw):
        return jax.grad(lambda *a: (ra.blockwise_attention(
            *a, block=8, **kw) * w).sum(), (0, 1, 2))(q, k, v)

    full = jax.grad(lambda *a: (ra.full_attention(
        a[0], *(jnp.repeat(t, 4, axis=2) for t in a[1:]), causal=True)
        * w).sum(), (0, 1, 2))(q, k, v)
    for a, b, c in zip(grads(), grads(mask=ra.CAUSAL), full):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        _close(a, c, 1e-5)


# -- the noise ----------------------------------------------------------------

def test_block_noise_is_the_law_and_its_key_alone(model):
    from swiftmpi_tpu.models.trainer import Trainer

    cfg, _params, _tokens, m, _ref = model
    big = dataclasses.replace(cfg, max_seq=2 * 4096)
    tokens = jax.random.randint(jax.random.key(1), (4, 4096), 0,
                                cfg.vocab_size - 1)
    tr = Trainer(big)
    noisy, weights = diffusion.block_noise(tr.noise_key(7), tokens, big)
    again = diffusion.block_noise(Trainer(big).noise_key(jnp.int32(7)),
                                  tokens, big)
    np.testing.assert_array_equal(np.asarray(noisy), np.asarray(again[0]))
    np.testing.assert_array_equal(np.asarray(weights), np.asarray(again[1]))
    other = diffusion.block_noise(tr.noise_key(8), tokens, big)[0]
    assert (np.asarray(other) != np.asarray(noisy)).mean() > 0.2

    noisy, weights, tokens = (np.asarray(a) for a in (noisy, weights, tokens))
    masked = weights > 0
    np.testing.assert_array_equal(noisy[masked], cfg.mask_token)
    np.testing.assert_array_equal(noisy[~masked], tokens[~masked])
    t = 1.0 / weights[masked]
    assert t.min() >= cfg.noise_eps and t.max() <= 1.0
    # one rate a block of 4, and a sequence's 1,024 blocks spread evenly
    # over [eps, 1]: the masked share is the mean rate, a half
    by_block = weights.reshape(4, 1024, 4)
    assert ((by_block == 0) | (by_block == by_block.max(-1, keepdims=True))
            ).all()
    assert abs(masked.mean() - 0.5) < 0.02
    # the reference's own function of the same law, from the same key
    r_noisy, r_weights = reference.noise(tr.noise_key(7), jnp.asarray(tokens),
                                         m)
    np.testing.assert_array_equal(np.asarray(r_noisy), noisy)
    np.testing.assert_allclose(np.asarray(r_weights), weights, rtol=1e-6)


# -- the head -----------------------------------------------------------------

def test_untied_head_and_embedding_both_move(model):
    from swiftmpi_tpu.models.trainer import Trainer

    cfg, _params, tokens, _m, _ref = model
    tr = Trainer(cfg, learning_rate=1e-2, warmup_steps=1, decay_steps=10)
    state = tr.init_state(jax.random.key(2))
    assert state.params["head"].shape == state.params["embed"].shape
    before = {k: np.asarray(state.params[k]) for k in ("head", "embed")}
    assert not np.array_equal(before["head"], before["embed"])
    for _ in range(2):
        state, loss = tr.step(state, tokens)
    assert math.isfinite(float(loss)) and int(state.step) == 2
    for k, was in before.items():
        assert not np.array_equal(was, np.asarray(state.params[k])), k


def test_tied_head_keeps_the_tree_as_it_was(model):
    """``tied_head=True`` (the default): no ``head`` leaf, the same key
    gives the same embedding and blocks as the untied tree's, and the
    logits go through the embedding."""
    cfg, params, tokens, _m, _ref = model
    tied = dataclasses.replace(cfg, tied_head=True, objective="next_token")
    assert tfm.TransformerConfig(vocab_size=8).tied_head
    p = tfm.init_params(jax.random.key(5), tied)
    assert set(p) == {"embed", "blocks", "ln_f"}
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(
            {k: v for k, v in params.items() if k != "head"})):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert tfm.head_matrix(p, tied) is p["embed"]
    assert tfm.head_matrix(params, cfg) is params["head"]
    logits, _aux = tfm.forward(p, tokens, tied)
    assert logits.shape == (B, S, tied.vocab_size)


# -- the share ----------------------------------------------------------------

def test_eight_shares_add_up_to_the_uncut_layer():
    """Experts 0-15, 16-31, ..., 112-127 each compute their part of a
    softmax top-8 layer; the parts add up to what the uncut reference gives
    for the whole layer, and every pick is computed exactly once."""
    p = moe.init_moe_params(jax.random.key(0), 16, 24, 128, gated=True)
    x = jax.random.normal(jax.random.key(1), (64, 16))
    whole, _ = moe.moe_ffn_reference(p, x, k=8, router="softmax")
    parts, held = 0.0, 0.0
    for lo in range(0, 128, 16):
        share = p._replace(w_in=p.w_in[lo:lo + 16], w_out=p.w_out[lo:lo + 16],
                           w_gate=p.w_gate[lo:lo + 16])
        y, _aux, st = moe.expert_layer(share, x, k=8, router="softmax",
                                       held=(lo, lo + 16), row_chunk=32)
        parts, held = parts + y, held + float(st.held)
        assert float(st.dropped) == 0.0
    _close(parts, whole, 1e-5)
    assert held == x.shape[0] * 8


# -- the scopes ---------------------------------------------------------------

def test_phase_of_books_the_noise_scope():
    assert costs.phase_of("jit(train_step)/noise/threefry2x32") == "noise"


def test_trainer_step_phase_map_has_the_noise_scope(model):
    from swiftmpi_tpu import obs
    from swiftmpi_tpu.models.trainer import Trainer

    cfg, _params, tokens, _m, _ref = model
    obs.reset_for_tests()
    obs.set_enabled(True)
    try:
        tr = Trainer(dataclasses.replace(cfg, remat=True,
                                         remat_policy="full"))
        state = tr.init_state(jax.random.key(0))
        state, _ = tr.run(state, [np.asarray(tokens)])
        pm = costs.phase_map("trainer_step")
        assert set(pm["phase"].values()) >= {
            "noise", "embed", "attention", "route", "experts", "head",
            "optimizer"}
        assert tr.train_metrics["dropped_picks_per_step"] == 0.0
        assert 0.0 < tr.train_metrics["held_pick_share"] <= 100.0
    finally:
        obs.reset_for_tests()
