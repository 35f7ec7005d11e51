"""Trinity-Mini's block stack — sliding-window and full attention behind one
mask contract, gated attention, sandwich norms, a shared expert beside a
scaled sigmoid router — against the plain reference
(benchmark/reference/swlm.py).

Seeded random weights at toy widths that keep every ratio of
``trinity-mini-ep16``: 8 query heads a KV head, ``d_head`` 16 over a 64-wide
residual, top-8 of 128 experts with 8 held, one shared expert, an untied
head, a window (24) that is no multiple of the tile (16).  float32 operands,
so program and reference agree to rounding.
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

from benchmark.families import swlm as family        # noqa: E402
from benchmark.lib import spec                       # noqa: E402
from benchmark.reference import swlm as reference    # noqa: E402
from swiftmpi_tpu.models import transformer as tfm   # noqa: E402
from swiftmpi_tpu.parallel import moe                # noqa: E402

# (the package re-exports the function ring_attention under the module's name)
ra = importlib.import_module("swiftmpi_tpu.parallel.ring_attention")

CELL = "trinity-ep16-16k-t16k"
B, S = 2, 64
#: (S, window, tile): a window that is no multiple of the tile, one that is,
#: one tile wide, narrower than a tile, one position, wider than the sequence
WINDOWS = [(64, 24, 16), (64, 32, 16), (64, 16, 16), (48, 7, 8), (32, 1, 8),
           (32, 100, 8), (64, 33, 64)]


@pytest.fixture(scope="module")
def model():
    cell = spec.load_cell(CELL, rehearse=True)
    traffic = dict(cell.traffic, sentence_tokens=S)
    cfg = dataclasses.replace(
        family.transformer_config(cell.config, traffic), remat=False)
    assert [k for k, _n in cfg.layer_groups()] == [
        ("sliding", "dense"), ("sliding", "moe"), ("full", "moe"),
        ("sliding", "moe")]
    assert [n for _k, n in cfg.layer_groups()] == [1, 1, 1, 2]
    assert cfg.n_heads // cfg.kv_heads == 8
    assert cfg.head_dim == 16 != cfg.d_model // cfg.n_heads
    assert (cfg.n_experts, cfg.moe_top_k, cfg.held) == (128, 8, (80, 88))
    assert (cfg.window, cfg.attn_block) == (24, 16)
    assert cfg.attn_gate and cfg.sandwich_norm and not cfg.tied_head
    assert (cfg.router, cfg.route_scale, cfg.n_shared_experts) == \
        ("sigmoid_bias", 2.826, 1)
    assert cfg.embed_scale == math.sqrt(cfg.d_model)
    params = tfm.init_params(jax.random.key(5), cfg)
    tokens = jax.random.randint(jax.random.key(6), (B, S), 0, cfg.vocab_size)
    m = reference.dims(cell.config)
    return cfg, params, tokens, m, reference.Reference(m)


def _close(got, want, tol=2e-5, floor=1e-30):
    """Frobenius distance over ``want``'s norm (or ``floor``, where a
    quantity may be exactly zero) under ``tol``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.linalg.norm(got - want) / max(np.linalg.norm(want), floor)
    assert err < tol, err


# -- the mask ------------------------------------------------------------------

def _masked_attention(q, k, v, see):
    """Plain softmax attention (B, S, H, D) with grouped KV heads under the
    explicit ``(S, S)`` mask ``see``."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("S,window,tile", WINDOWS)
def test_window_mask_against_the_explicit_mask(S, window, tile):
    """Forward and gradients of blockwise attention under ``WindowMask``,
    8 query heads a KV head, against plain attention with the boolean
    mask."""
    kq, kk, kv, kw = jax.random.split(jax.random.key(S + window), 4)
    q = jax.random.normal(kq, (2, S, 8, 16))
    k = jax.random.normal(kk, (2, S, 1, 16))
    v = jax.random.normal(kv, (2, S, 1, 16))
    w = jax.random.normal(kw, q.shape)
    i = np.arange(S)
    see = jnp.asarray((i[:, None] >= i[None]) & (i[:, None] - i[None]
                                                 < window))
    assert bool(jnp.array_equal(see, reference.visible(
        jnp.arange(S), jnp.arange(S), window)))
    mask = ra.WindowMask(window)

    def prog(q, k, v):
        return ra.blockwise_attention(q, k, v, block=tile, mask=mask)

    _close(prog(q, k, v), _masked_attention(q, k, v, see))
    gp = jax.grad(lambda *a: (prog(*a) * w).sum(), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (_masked_attention(*a, see) * w).sum(),
                  (0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        _close(a, b, 1e-4, floor=1.0)    # window 1: dq and dk are zero


@pytest.mark.parametrize("S,window,tile", WINDOWS)
def test_window_tile_lists_are_transposes_and_cover(S, window, tile):
    """``key_tiles`` (the one list the forward and, since PR 39, the
    backward walk) lists each tile pair once; the pairs hold every visible
    pair; every query sees a key in its list; and a tile pair outside the
    list holds no visible pair."""
    mask = ra.WindowMask(window)
    size = mask.tile(tile, S)
    n, pos = S // size, np.arange(size)

    by_query = []
    for i in range(n):
        lo, hi, at = mask.key_tiles(i, n, size)
        by_query += [(i, int(at(t))) for t in range(int(lo), int(hi))]
    assert len(set(by_query)) == len(by_query)
    for i in range(n):
        for j in range(n):
            see = np.asarray(mask.visible((i * size + pos)[:, None],
                                          (j * size + pos)[None]))
            if (i, j) in by_query:
                assert see.any()
            else:
                assert not see.any(), (i, j)
    assert all((i, i) in by_query for i in range(n))   # a query sees itself


def test_window_mask_refuses_an_empty_window():
    with pytest.raises(ValueError, match="sees itself"):
        ra.WindowMask(0)


def test_real_size_tile_counts():
    """S 16,384, window 2,048, tile 512: a sliding layer folds 150 tile
    pairs, a full one 528 (ISSUE 37's counts)."""
    n, size = 32, 512
    w, c = ra.WindowMask(2048), ra.CAUSAL
    count = lambda m: sum(int(hi) - int(lo) for lo, hi, _ in
                          (m.key_tiles(i, n, size) for i in range(n)))
    assert (count(w), count(c)) == (150, 528)


# -- the configuration's refusals -------------------------------------------------

@pytest.mark.parametrize("kwargs,match", [
    (dict(layer_ops=("sliding", "banded")), "unknown layer kinds"),
    (dict(layer_ops=("sliding", "full"), window=8, attention="full"),
     "needs attention 'blockwise'"),
    (dict(layer_ops=("sliding", "full"), attention="blockwise"),
     "needs window >= 1"),
    (dict(layer_ops=("sliding", "full"), window=8, attention="blockwise",
          objective="block_diffusion"), "brings its own mask"),
], ids=["unknown-kind", "window-without-blockwise", "no-window",
        "two-masks"])
def test_config_refuses_by_name(kwargs, match):
    with pytest.raises(ValueError, match=match):
        tfm.TransformerConfig(vocab_size=32, n_layers=2, **kwargs)


def test_new_fields_default_to_the_stack_as_it_was():
    fields = tfm.TransformerConfig.__dataclass_fields__
    for name, default in [("window", 0), ("attn_gate", False),
                          ("sandwich_norm", False), ("embed_scale", 1.0),
                          ("n_shared_experts", 0), ("route_scale", 1.0)]:
        assert fields[name].default == default, name
    cfg = tfm.TransformerConfig(vocab_size=32, n_layers=2, n_experts=4,
                                layer_ops=("attention", "conv"),
                                layer_ffns=("moe", "dense"))
    blocks = tfm.init_params(jax.random.key(0), cfg)["blocks"]
    assert set(blocks[0]) == {"ln1", "ln2", "wq", "wk", "wv", "wo", "moe"}
    assert set(blocks[1]) == {"ln1", "ln2", "conv_in", "conv_out", "conv_w",
                              "w_gate", "w_up", "w_down"}


# -- the router -----------------------------------------------------------------

@pytest.mark.parametrize("kind", moe.ROUTERS)
def test_route_scale_one_is_todays_and_a_scale_multiplies(kind):
    x = jax.random.normal(jax.random.key(1), (64, 32))
    router = jax.random.normal(jax.random.key(2), (32, 128)) * 0.3
    bias = jax.random.normal(jax.random.key(3), (128,)) * 0.01 \
        if kind == "sigmoid_bias" else None
    # today's weights, written out
    logits = jnp.dot(x, router, precision=lax.Precision.HIGHEST)
    if kind == "softmax":
        top, sel0 = lax.top_k(jax.nn.softmax(logits, axis=-1), 8)
        want = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
    else:
        s = jax.nn.sigmoid(logits)
        _, sel0 = lax.top_k(s + bias, 8)
        top = jnp.take_along_axis(s, sel0, axis=-1)
        want = top / (top.sum(-1, keepdims=True) + 1e-6)
    sel, gates, dens, proxy = moe.route(x, router, bias, 8, kind)
    sel1, gates1, dens1, proxy1 = moe.route(x, router, bias, 8, kind, 1.0)
    for a, b in [(sel, sel0), (gates, want), (sel1, sel0), (gates1, want),
                 (dens1, dens), (proxy1, proxy)]:
        assert np.array_equal(np.asarray(a), np.asarray(b))     # bit for bit
    sel2, gates2, *_ = moe.route(x, router, bias, 8, kind, 2.826)
    assert np.array_equal(np.asarray(sel2), np.asarray(sel0))
    np.testing.assert_allclose(np.asarray(gates2.sum(-1)), 2.826, rtol=1e-5)
    assert np.array_equal(np.asarray(gates2),
                          np.asarray(want * jnp.float32(2.826)))


# -- the layers -------------------------------------------------------------------

@pytest.mark.parametrize("index", range(4),
                         ids=["sliding+dense", "sliding+moe", "full+moe",
                              "sliding+moe-scanned"])
def test_layer_forward_and_gradient(model, index):
    """One layer of each run: both attention kinds (window 24 over tiles of
    16, RoPE on the sliding one alone), the gate, the four norms, the dense
    FFN, and the expert layer with its shared expert and scaled weights."""
    cfg, params, _tokens, m, _ref = model
    kind = cfg.layer_groups()[index][0]
    blk = jax.tree.map(lambda a: a[0], params["blocks"][index])
    x = jax.random.normal(jax.random.key(7), (B, S, cfg.d_model))
    w = jax.random.normal(jax.random.key(8), x.shape)

    def prog(blk, x):
        return tfm.block_apply(blk, x, cfg, kind=kind)[0]

    def plain(blk, x):
        with reference.highest():
            return jnp.stack([reference.layer(blk, xb, kind, m) for xb in x])

    _close(prog(blk, x), plain(blk, x))
    gp = jax.grad(lambda b, a: (prog(b, a) * w).sum(), (0, 1))(blk, x)
    gr = jax.grad(lambda b, a: (plain(b, a) * w).sum(), (0, 1))(blk, x)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        _close(a, b, 1e-4)


def test_the_full_layer_has_no_position_embedding(model):
    """A full layer's attention output at a position depends on the set of
    earlier tokens, not their order; a sliding layer's (RoPE) does."""
    cfg, params, _tokens, _m, _ref = model
    x = jax.random.normal(jax.random.key(9), (1, S, cfg.d_model))
    flipped = jnp.concatenate([x[:, :S - 1][:, ::-1], x[:, S - 1:]], axis=1)
    last = {}
    for index, op in ((1, "sliding"), (2, "full")):
        blk = jax.tree.map(lambda a: a[0], params["blocks"][index])
        big = dataclasses.replace(cfg, window=S)     # the window holds all
        run = lambda a: tfm._operator(blk, a, big, None, "seq", op)[0, -1]
        last[op] = float(jnp.abs(run(x) - run(flipped)).max()
                         / jnp.abs(run(x) - x[0, -1]).max())
    assert last["full"] < 1e-5 < 1e-2 < last["sliding"]


def test_hidden_states_are_the_trunk(model):
    cfg, params, tokens, m, ref = model
    hs = tfm.hidden_states(params, tokens, cfg)
    assert len(hs) == 2 * cfg.n_layers + 1
    _close(hs[0], params["embed"][tokens] * 8.0, 1e-7)    # sqrt(64)
    x, _aux, _stats = tfm.trunk(params, tokens, cfg)
    _close(x, tfm._rms_norm(hs[-1], params["ln_f"], cfg.norm_eps), 1e-6)
    for i, (part, blk) in enumerate(ref.halves(params)):
        err, gap = ref.half_error(part, blk, hs[i][0], hs[i + 1][0])
        assert float(err.max()) < 1e-4, (i, part)
        assert gap.shape == (S,)


@pytest.mark.parametrize("remat,chunk", [(False, 0), (True, 16)],
                         ids=["plain", "remat+chunked-loss"])
def test_whole_loss_and_gradient(model, remat, chunk):
    """Loss and every gradient of the five-layer stack in its four scanned
    runs against the reference's sequence-by-sequence, half-layer-by-half-
    layer pass."""
    cfg, params, tokens, _m, ref = model
    cfg = dataclasses.replace(cfg, remat=remat, remat_policy="full",
                              loss_chunk=chunk)
    loss, grads = jax.value_and_grad(tfm.lm_loss)(params, tokens, cfg,
                                                  aux_weight=0.0)
    want, gref = ref.loss_and_grads(params, np.asarray(tokens))
    assert abs(float(loss) - want) < 1e-5 * want
    assert abs(ref.loss(params, np.asarray(tokens)) - want) < 1e-6 * want
    assert set(grads) == set(gref) == {"embed", "head", "blocks", "ln_f"}
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(grads)]
    for path, a, b in zip(paths, jax.tree.leaves(grads),
                          jax.tree.leaves(gref)):
        if "bias" in path:
            continue                     # a buffer: zero on both sides
        _close(a, b, 2e-4)
    for name in ("wg", "ln1_post", "ln2_post", "shared_gate", "shared_down"):
        assert float(jnp.abs(grads["blocks"][1][name]).max()) > 0, name


# -- the shares add up ----------------------------------------------------------------

def test_sixteen_shares_and_the_shared_expert_once_are_the_whole_layer(model):
    """The routed parts the 16 chips of the deployment compute (each told
    the 8 experts it holds, each routing over all 128) plus the shared
    expert, counted once, equal the uncut expert layer of the reference."""
    cfg, _params, _tokens, m, _ref = model
    whole = dataclasses.replace(cfg, experts_held=(),
                                layer_ops=("sliding",), layer_ffns=("moe",),
                                n_layers=1)
    blk = tfm._init_block(jax.random.key(11), whole, "sliding", "moe")
    full = blk["moe"]
    assert full.w_in.shape[0] == 128
    u = jax.random.normal(jax.random.key(12), (96, cfg.d_model))

    routed, picks = 0.0, 0.0
    for chip in range(16):
        lo, hi = 8 * chip, 8 * chip + 8
        share = full._replace(w_in=full.w_in[lo:hi], w_out=full.w_out[lo:hi],
                              w_gate=full.w_gate[lo:hi])
        y, _aux, stats = moe.expert_layer(
            share, u, k=cfg.moe_top_k, router=cfg.router, held=(lo, hi),
            route_scale=cfg.route_scale)
        routed, picks = routed + y, picks + float(stats.held)
        assert float(stats.dropped) == 0.0
    assert picks == 96 * 8                       # every pick on one chip
    shared = tfm._swiglu(u, blk["shared_gate"], blk["shared_up"],
                         blk["shared_down"], whole)
    with reference.highest():
        want, _gap = reference.expert_ffn(blk, u, dict(m, held=(0, 128)))
        _close(routed + shared, want)
        # and one share alone is the reference's share
        got = moe.expert_layer(
            full._replace(w_in=full.w_in[:8], w_out=full.w_out[:8],
                          w_gate=full.w_gate[:8]), u, k=8,
            router=cfg.router, held=(0, 8),
            route_scale=cfg.route_scale)[0] + shared
        _close(got, reference.expert_ffn(blk, u, dict(m, held=(0, 8)))[0])
    # the weights of a token's eight picks sum to the published scale
    sel, gates, *_ = moe.route(u, full.router, full.bias, 8, cfg.router,
                               cfg.route_scale)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.826, rtol=1e-5)
