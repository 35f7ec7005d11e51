"""Learned sparse attention (Keye-VL-2.0's language stack) against the plain
reference (benchmark/reference/salm.py), and the mask contract it opened.

Seeded random weights at toy widths that keep every ratio of
``keye-vl-2.0-30b-a3b-ep8``: 8 query heads a KV head, ``d_head`` 16 over a
64-wide residual, 4 index heads of 8 on one index key, top 24 of 64
positions (so most queries drop keys), top-8 of 128 experts with 16 held, an
untied head.  float32 operands, so program and reference agree to rounding
and the two selections are identical.
"""

import dataclasses
import hashlib
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.families import salm as family        # noqa: E402
from benchmark.lib import spec                       # noqa: E402
from benchmark.reference import salm as reference    # noqa: E402
from swiftmpi_tpu.models import transformer as tfm   # noqa: E402
from swiftmpi_tpu.models.diffusion import BlockDiffusionMask  # noqa: E402
from swiftmpi_tpu.parallel import moe                # noqa: E402
from swiftmpi_tpu.parallel import sparse_attention as sa  # noqa: E402

# (the package re-exports the function ring_attention under the module's name)
ra = importlib.import_module("swiftmpi_tpu.parallel.ring_attention")

CELL = "keye2-ep8-16k-t16k"
B, S = 2, 64
GIB = 2 ** 30


@pytest.fixture(scope="module")
def model():
    cell = spec.load_cell(CELL, rehearse=True)
    config = dict(cell.config, num_hidden_layers=2)
    traffic = dict(cell.traffic, sentence_tokens=S)
    cfg = dataclasses.replace(family.transformer_config(config, traffic),
                              remat=False)
    assert cfg.layer_groups() == [(("sparse", "moe"), 2)]
    assert cfg.n_heads // cfg.kv_heads == 8
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (4, 8, 24)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.held) == (128, 8, (0, 16))
    assert cfg.attention == "blockwise" and S // cfg.attn_block == 4
    params = tfm.init_params(jax.random.key(5), cfg)
    tokens = jax.random.randint(jax.random.key(6), (B, S), 0, cfg.vocab_size)
    m = reference.dims(config)
    return cfg, params, tokens, m, reference.Reference(m)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
    assert err < tol, err


def _closed_form(S, k):
    return sum(min(t + 1, k) for t in range(S))


def _loss(params, tokens, cfg):
    return tfm.lm_loss_and_stats(params, tokens, cfg, aux_weight=0.0)


# -- the layer against the reference -------------------------------------------

def test_layer_forward_selection_and_index_loss(model):
    """One sparse layer on seeded weights: the selection is the reference's
    bit for bit (ties and all), the layer's output, its index loss and the
    pairs kept are the reference's."""
    cfg, params, tokens, m, ref = model
    hs = tfm.hidden_states(params, tokens, cfg)
    blk = jax.tree.map(lambda a: a[0], params["blocks"][0])
    probe = tfm.sparse_probe(blk, hs[0], cfg)
    keep = sa.unpack(probe["bits"], cfg.attn_block)
    losses = []
    for b in range(B):
        want_keep = ref.selection(blk, hs[0][b])
        assert bool((keep[b] == want_keep).all())
        want, _gap, li = ref._half["sparse"](blk, hs[0][b], None)
        _close(hs[1][b], want)
        losses.append(float(li))
    np.testing.assert_allclose(float(probe["index_loss"]), np.mean(losses),
                               rtol=1e-5)
    assert int(probe["kept"]) == B * _closed_form(S, 24) == int(keep.sum())


@pytest.mark.parametrize("remat", [False, True])
def test_whole_objective_and_every_gradient(model, remat):
    """``L_LM + sum LI`` and its gradient in every parameter, through
    ``lm_loss_and_stats`` (what the Trainer differentiates), against
    ``jax.vjp`` of the reference's equations."""
    cfg, params, tokens, _m, ref = model
    cfg = dataclasses.replace(cfg, remat=remat, remat_policy="full",
                              loss_chunk=32)
    (loss, (_stats, parts)), grads = jax.jit(jax.value_and_grad(
        lambda p: _loss(p, tokens, cfg), has_aux=True))(params)
    (want, main, index), want_grads = ref.loss_and_grads(params,
                                                         np.asarray(tokens))
    np.testing.assert_allclose(float(loss), want, rtol=1e-5)
    np.testing.assert_allclose(float(parts["main_loss"]), main, rtol=1e-5)
    np.testing.assert_allclose(float(parts["index_loss"]), index, rtol=1e-5)
    np.testing.assert_allclose(float(parts["index_loss_per_layer"]),
                               index / 2, rtol=1e-5)
    np.testing.assert_allclose(float(parts["selected_keys_per_query"]),
                               _closed_form(S, 24) / S, rtol=1e-6)
    np.testing.assert_allclose(float(parts["selected_pair_share"]),
                               100 * _closed_form(S, 24) / (S * (S + 1) / 2),
                               rtol=1e-6)
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, g in jax.tree_util.tree_leaves_with_path(want_grads):
        _close(got[path], g, 5e-5)
    assert len(got) == len(jax.tree.leaves(want_grads))


def test_each_loss_trains_its_own_parameters(model):
    """The language-model loss reaches nothing of the indexer (its input is
    detached, the selection is a constant of the step); the index loss
    reaches the indexer alone."""
    cfg, params, tokens, _m, _ref = model
    indexer = {"wq_idx", "wk_idx", "w_idx", "idx_ln_g", "idx_ln_b"}

    def norms(part):
        g = jax.grad(lambda p: _loss(p, tokens, cfg)[1][1][part])(params)
        return {jax.tree_util.keystr(path): float(jnp.abs(a).max())
                for path, a in jax.tree_util.tree_leaves_with_path(g)}

    for part, moves in (("main_loss", False), ("index_loss", True)):
        for path, size in norms(part).items():
            mine = any(f"'{name}'" in path for name in indexer)
            assert (size > 0) == (mine == moves), (part, path, size)


def test_blockwise_equals_full(model):
    """``attention="blockwise"`` (tiles, packed bits, the hand-written
    walks) and ``"full"`` (every (S, S) array whole, automatic
    differentiation): one objective, one gradient."""
    cfg, params, tokens, _m, _ref = model
    out = {}
    for variant in ("blockwise", "full"):
        c = dataclasses.replace(cfg, attention=variant)
        out[variant] = jax.jit(jax.value_and_grad(
            lambda p, c=c: _loss(p, tokens, c)[0]))(params)
    np.testing.assert_allclose(float(out["blockwise"][0]),
                               float(out["full"][0]), rtol=1e-6)
    for a, b in zip(*(jax.tree.leaves(out[v][1])
                      for v in ("blockwise", "full"))):
        _close(a, b, 2e-5)


# -- the selection's edges ------------------------------------------------------

def _reference_keep(scores, causal, k):
    """A stable descending sort's first k, as the reference makes it."""
    masked = np.where(causal, scores, -np.inf)
    order = np.argsort(-masked, axis=-1, kind="stable")[..., :k]
    keep = np.zeros(scores.shape, bool)
    np.put_along_axis(keep, order, True, axis=-1)
    return keep & causal


@pytest.mark.parametrize("case", ["random", "quantised", "constant", "zeros",
                                  "short"])
def test_top_rows_is_the_stable_sort(case):
    """The bisection over ordered bits against a stable sort: rows with
    fewer causal keys than ``k`` keep them all; equal scores at the cut take
    the lower positions; ``-0.0`` ties with ``0.0``; ``S <= k`` keeps every
    causal key."""
    rng = np.random.default_rng(7)
    n, S, k = 40, 40, 9
    scores = rng.standard_normal((2, n, S)).astype(np.float32)
    if case == "quantised":          # many equal scores, both signs
        scores = np.round(scores * 2) / 2
    elif case == "constant":         # every key ties: the first k positions
        scores[:] = 0.25
    elif case == "zeros":            # relu's zeros under weights of both signs
        scores = np.where(rng.random(scores.shape) < 0.7,
                          np.where(rng.random(scores.shape) < 0.5, 0.0, -0.0),
                          scores).astype(np.float32)
    elif case == "short":
        k = 64
    causal = np.tril(np.ones((n, S), bool))
    got = np.asarray(sa.top_rows(
        jnp.where(causal, scores, -jnp.inf), jnp.asarray(causal), k))
    want = _reference_keep(scores, causal, k)
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(np.arange(n) + 1, k)).all()
    if case == "constant":
        assert got[0, -1, :k].all() and not got[0, -1, k:].any()
    if case == "short":
        assert (got == causal).all()


@pytest.mark.parametrize("S_,k,block", [(48, 10, 16), (48, 10, 8),
                                        (40, 7, 40), (24, 64, 8)])
def test_select_packs_what_top_rows_keeps(S_, k, block):
    """``select`` a query tile at a time: the packed bits are the dense
    selection's (``S`` need not be a multiple of ``k``; a tile of 8 or 40
    packs 8 queries a word, of 16 sixteen), its count the closed form, its
    log-sum-exp the selected scores'."""
    rng = np.random.default_rng(3)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    qi, w, ki = mk(2, S_, 3, 4), mk(2, S_, 3), mk(2, S_, 4)
    bits, lse, kept = sa.select(qi, w, ki, k, block)
    scores = sa.index_tile(qi, w, ki)
    causal = jnp.tril(jnp.ones((S_, S_), bool))
    want = sa.top_rows(jnp.where(causal, scores, -jnp.inf), causal, k)
    assert bool((sa.unpack(bits, block) == want).all())
    assert int(kept) == 2 * _closed_form(S_, k)
    np.testing.assert_allclose(
        lse, jax.nn.logsumexp(jnp.where(want, scores, -jnp.inf), -1),
        rtol=1e-6)


def test_keeping_every_key_is_causal_attention_bit_for_bit():
    """``S <= k``: the selection is the causal triangle, and attention
    under it — the mask read from packed bits — is causal attention to the
    bit, outputs and gradients."""
    rng = np.random.default_rng(11)
    B_, S_, H, Hkv, D = 2, 32, 4, 2, 8
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v, c = mk(B_, S_, H, D), mk(B_, S_, Hkv, D), mk(B_, S_, Hkv, D), \
        mk(B_, S_, H, D)
    qi, w, ki = mk(B_, S_, 3, 4), mk(B_, S_, 3), mk(B_, S_, 4)
    bits, _lse, kept = sa.select(qi, w, ki, 64, 8)
    assert int(kept) == B_ * S_ * (S_ + 1) // 2

    def run(**mask):
        o, pull = jax.vjp(lambda *a: ra.blockwise_attention(
            *a, block=8, **mask), q, k, v)
        return (o, *pull(c))

    for a, b in zip(run(mask=sa.SELECTED, mask_data=bits), run()):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_tiled_operator_equals_the_dense_one():
    """``sparse_attention`` (tile walks, custom gradients) against
    ``sparse_attention_dense`` (whole matrices, automatic differentiation),
    where most queries drop keys: outputs, index loss, count and every
    gradient, with the index loss weighted."""
    rng = np.random.default_rng(0)
    B_, S_, H, Hkv, D, HI, dI, k = 2, 48, 4, 2, 8, 3, 4, 10
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    args = (mk(B_, S_, H, D), mk(B_, S_, Hkv, D), mk(B_, S_, Hkv, D),
            mk(B_, S_, HI, dI), mk(B_, S_, HI), mk(B_, S_, dI))
    c = mk(B_, S_, H, D)

    def scalar(f):
        def g(*a):
            o, li, n = f(*a)
            return (o * c).sum() + 3.0 * li, (o, li, n)
        return jax.value_and_grad(g, argnums=range(6), has_aux=True)

    (_, (od, lid, nd)), gd = scalar(
        lambda *a: sa.sparse_attention_dense(*a, topk=k))(*args)
    for block in (16, 8, 48):
        (_, (o, li, n)), g = scalar(
            lambda *a: sa.sparse_attention(*a, topk=k, block=block)[:3])(
                *args)
        _close(o, od)
        np.testing.assert_allclose(float(li), float(lid), rtol=1e-5)
        assert int(n) == int(nd) == B_ * _closed_form(S_, k)
        for a, b in zip(g, gd):
            _close(a, b, 5e-5)


def test_bf16_operands_keep_index_sums_and_statistics_in_float32():
    """With bf16 operands the index scores' product accumulates in float32
    and ReLU, weights and the sum over heads stay float32 (the chip run's
    second readings cannot tell a bf16 index sum or bf16 statistics from the
    operands' own rounding — PERF.md section 6, PR 49 — so this holds
    them): the tile equals the float32 arithmetic on the rounded operands,
    a bf16 sum does not; the selection's and the attention's log-sum-exp
    are float32."""
    rng = np.random.default_rng(2)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    qi, w, ki = mk(1, 32, 16, 64), mk(1, 32, 16), mk(1, 32, 64)
    lo = lambda a: a.astype(jnp.bfloat16)
    got = sa.index_tile(lo(qi), w, lo(ki))
    assert got.dtype == jnp.float32
    exact = sa.index_tile(lo(qi).astype(jnp.float32), w,
                          lo(ki).astype(jnp.float32))
    np.testing.assert_allclose(got, exact, rtol=1e-5, atol=1e-5)
    pre = jnp.einsum("bqhd,bkd->bhqk", lo(qi), lo(ki),
                     preferred_element_type=jnp.float32)
    rough = (lo(jax.nn.relu(pre)) * lo(jnp.swapaxes(w, 1, 2)[..., None])
             ).sum(axis=1).astype(jnp.float32)
    assert float(jnp.abs(rough - exact).max()) \
        > 100 * float(jnp.abs(got - exact).max())
    _bits, lse_i, _kept = sa.select(lo(qi), w, lo(ki), 8, 8)
    q, k, v = lo(mk(1, 32, 4, 8)), lo(mk(1, 32, 2, 8)), lo(mk(1, 32, 2, 8))
    _o, lse = ra.blockwise_attention(q, k, v, block=8, with_lse=True)
    assert lse_i.dtype == lse.dtype == jnp.float32


# -- the configuration ----------------------------------------------------------

BASE = dict(vocab_size=64, d_model=32, n_layers=1, n_heads=4, n_kv_heads=2,
            layer_ops=("sparse",), layer_ffns=("dense",),
            attention="blockwise", index_heads=2, index_head_dim=4,
            index_topk=8)


@pytest.mark.parametrize("kwargs,match", [
    (dict(index_heads=0), "index_heads not set"),
    (dict(index_topk=0, index_head_dim=0),
     "index_head_dim, index_topk not set"),
    (dict(index_head_dim=3), "index_head_dim 3 is odd"),
    (dict(attention="ring"), "needs attention 'blockwise' or 'full'"),
    (dict(objective="block_diffusion"), "objective 'block_diffusion'"),
    (dict(mtp_layers=1), "mtp_layers with a last layer that is 'sparse'"),
])
def test_config_refuses_by_name(kwargs, match):
    with pytest.raises(ValueError, match=re.escape(match)):
        tfm.TransformerConfig(**{**BASE, **kwargs})


def test_new_fields_are_off_by_default_and_a_sparse_layer_takes_no_mask():
    cfg = tfm.TransformerConfig(vocab_size=8)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (0, 0, 0)
    assert "sparse" in tfm.ATTENTION_OPS and "sparse" in tfm.OPS
    cfg = tfm.TransformerConfig(**BASE)
    params = tfm.init_params(jax.random.key(0), cfg)
    blk = jax.tree.map(lambda a: a[0], params["blocks"][0])
    assert {"wq_idx", "wk_idx", "w_idx", "idx_ln_g", "idx_ln_b"} <= set(blk)
    x = jnp.zeros((1, 16, 32))
    with pytest.raises(ValueError, match="composes with no other mask"):
        tfm.block_apply(blk, x, cfg, mask=ra.WindowMask(4))
    # the stack's other kinds keep their parameters and their carry
    plain = tfm.TransformerConfig(**{**BASE, "layer_ops": ("attention",)})
    assert "wq_idx" not in tfm.init_params(jax.random.key(0),
                                           plain)["blocks"][0]


# -- the share ------------------------------------------------------------------

def test_eight_shares_add_up_to_the_uncut_layer(model):
    """The guide's share test: experts 0-15, ..., 112-127 each compute
    their part of a layer's expert half behind the same sparse half (whole
    on every chip, counted once); the parts add up to what the uncut
    reference gives for the whole layer."""
    cfg, params, tokens, m, _ref = model
    blk = jax.tree.map(lambda a: a[0], params["blocks"][0])
    whole = moe.init_moe_params(jax.random.key(2), cfg.d_model, cfg.d_expert,
                                128, gated=True, std=0.3)
    x = tfm.hidden_states(params, tokens, cfg)[0]
    uncut = dict(blk, moe=whole)
    m_all = dict(m, held=(0, 128))
    x = x[:1]
    want = reference.half(
        uncut, reference.half(uncut, x[0], "sparse", m_all)[0], "moe",
        m_all)[0][None]
    mid = tfm._operator(blk, x, cfg, None, "seq", "sparse")
    parts = 0.0
    for lo in range(0, 128, 16):
        c = dataclasses.replace(cfg, experts_held=(lo, lo + 16))
        share = dict(blk, moe=whole._replace(
            w_in=whole.w_in[lo:lo + 16], w_out=whole.w_out[lo:lo + 16],
            w_gate=whole.w_gate[lo:lo + 16]))
        y, _aux, stats = tfm._ffn(share, mid, c, None, "expert", "moe")
        assert float(stats.dropped) == 0.0
        parts = parts + (y - mid)
    _close(mid + parts, want, 1e-5)


# -- the mask contract, as it was -----------------------------------------------

#: sha256 (first 16 hex digits) of the float32 bytes of ``blockwise_attention``'s
#: output and its three gradients on the seeded inputs below, computed on this
#: container's CPU backend at the parent of PR 49, before the contract took
#: data: float32 inputs, bfloat16 inputs
PINNED = {"causal": ("c91e5822e6257bc1", "62238f81e692f5d3"),
          "window": ("05fe5334ff31df3b", "4c8053284ef56e0c"),
          "diffusion": ("5bb482df1b749663", "0d2ab27bae5ebde5")}
MASKS = {"causal": ra.CAUSAL, "window": ra.WindowMask(24),
         "diffusion": BlockDiffusionMask(32, 4)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(MASKS))
def test_positional_masks_are_unchanged_to_the_bit(name, dtype):
    rng = np.random.default_rng(49)
    B_, S_, H, Hkv, D = 2, 64, 4, 2, 8
    q, k, v, c = (jnp.asarray(rng.standard_normal(shape), dtype)
                  for shape in ((B_, S_, H, D), (B_, S_, Hkv, D),
                                (B_, S_, Hkv, D), (B_, S_, H, D)))
    o, pull = jax.vjp(lambda *a: ra.blockwise_attention(
        *a, block=16, mask=MASKS[name]), q, k, v)
    digest = hashlib.sha256()
    for a in (o, *pull(c)):
        digest.update(np.asarray(a, np.float32).tobytes())
    assert digest.hexdigest()[:16] == PINNED[name][dtype == jnp.bfloat16]


def test_lse_is_the_visible_scores_and_carries_no_gradient():
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal(s), jnp.float32)
               for s in ((1, 32, 4, 8), (1, 32, 2, 8), (1, 32, 2, 8)))
    o, lse = ra.blockwise_attention(q, k, v, block=8, with_lse=True)
    assert np.array_equal(np.asarray(o),
                          np.asarray(ra.blockwise_attention(q, k, v, block=8)))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, 2)) / np.sqrt(8)
    s = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), s, -jnp.inf)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(s, -1).transpose(0, 2, 1),
                               rtol=1e-5)
    g = jax.grad(lambda q: ra.blockwise_attention(
        q, k, v, block=8, with_lse=True)[1].sum())(q)
    assert float(jnp.abs(g).max()) == 0.0


# -- the real size, compiled for the chip ---------------------------------------

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_keye2_ep8_trainer_step_fits_one_chip(topo):
    """The real ``trainer_step`` of ``keye2-ep8-16k-t16k`` — 4 sparse
    layers at the published widths (32 heads of 128 on 4 KV heads, 16 index
    heads of 64, top 2,048), 16 of 128 experts, an untied head, one packed
    sequence of 16,384 — on one v5e chip: the compiler's memory report fits
    15.75 GiB (13.06 at PR 49), the grouped products are the compiler's
    ``ragged-dot`` kernels, and no float buffer has two dims of a sequence's
    length: the only ``(S, .., S)`` array is the selection's packed bits."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from swiftmpi_tpu.models.trainer import Trainer

    cell = spec.load_cell(CELL)
    cfg = family.transformer_config(cell.config, cell.traffic)
    S_ = int(cell.traffic["sentence_tokens"])
    one = SingleDeviceSharding(topo.devices[0])
    trainer = Trainer(cfg, **family.trainer_kwargs(cell.config))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda k: trainer.init_state(k).tree(),
                       jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((1, S_), jnp.int32, sharding=one)
    assert sum(a.size for a in jax.tree.leaves(state["params"])) \
        == 465_391_104                        # ISSUE 49's count, 7.45 GB x 16 B
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = trainer._build_step().lower(
            state["params"], state["opt_state"], state["step"],
            tokens).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= 14.5 * GIB, f"{total / GIB:.2f} GiB"
    assert mem.alias_size_in_bytes >= 0.99 * mem.output_size_in_bytes
    text = compiled.as_text()
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*?'
                         r'op_name="([^"]*)"', text)
    # the attention's forward walk under the data mask is the kernel (PR
    # 50): the scanned layer's body, forward and recomputed, under the
    # layer's scope and none of the indexer's
    walks = [n for n in kernels if n.endswith("/attn_fwd_tiles/pallas_call")]
    assert len(walks) == 2
    assert all("/sparse_attention/" in n and "indexer" not in n
               for n in walks)
    kernels = [n for n in kernels if n not in walks]
    assert set(kernels) == {"ragged-dot-none", "ragged-dot-metadata"}
    # 3 products forward and 9 backward a chunk body; the one scanned layer
    # body holds the loop's (a whole chunk) and the half rung's (PR 53)
    assert kernels.count("ragged-dot-none") == (1 + 1) * 12
    for dtype, dims in set(re.findall(r"= (\w+)\[([\d,]+)\]", text)):
        big = [int(d) for d in dims.split(",") if int(d) >= S_]
        assert len(big) < 2 or dtype == "u32", f"{dtype}[{dims}]"
    for scope in ("sparse_attention", "indexer", "index_select"):
        assert f"/{scope}/" in text
