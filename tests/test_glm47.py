"""GLM-4.7-Flash's block stack — multi-head latent attention (low-rank
queries, a compressed KV re-expanded per head, one decoupled RoPE key shared
by every head), a scaled sigmoid router beside a shared expert, and a
multi-token-prediction module behind the trunk — against the plain reference
(benchmark/reference/mlalm.py).

Seeded random weights at toy widths that keep every ratio of
``glm-4.7-flash-ep8``: ``qk_rope_dim`` a quarter of the head, ``v_head_dim``
= ``qk_nope_dim + qk_rope_dim``, ranks narrower than the residual, one KV
head a query head (``G`` = 1), top-4 of 64 experts with 8 held, one shared
expert, an untied head.  float32 operands, so program and reference agree to
rounding.
"""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.families import mlalm as family        # noqa: E402
from benchmark.lib import spec                        # noqa: E402
from benchmark.reference import mlalm as reference    # noqa: E402
from swiftmpi_tpu.models import transformer as tfm    # noqa: E402
from swiftmpi_tpu.parallel import moe                 # noqa: E402
from swiftmpi_tpu.parallel.ring_attention import full_attention  # noqa: E402

CELL = "glm47f-ep8-8k-t8k"
B, S = 2, 64
LATENT = dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=12, qk_rope_dim=4,
              v_head_dim=16)


@pytest.fixture(scope="module")
def model():
    cell = spec.load_cell(CELL, rehearse=True)
    traffic = dict(cell.traffic, sentence_tokens=S)
    cfg = dataclasses.replace(
        family.transformer_config(cell.config, traffic), remat=False)
    assert cfg.layer_groups() == [(("latent", "dense"), 1),
                                  (("latent", "moe"), 4)]
    assert (cfg.n_heads, cfg.kv_heads) == (4, 4)              # G = 1
    assert cfg.qk_rope_dim * 4 == cfg.qk_nope_dim + cfg.qk_rope_dim \
        == cfg.v_head_dim == 16
    assert max(cfg.q_lora_rank, cfg.kv_lora_rank) < cfg.d_model
    assert (cfg.n_experts, cfg.moe_top_k, cfg.held) == (64, 4, (16, 24))
    assert (cfg.router, cfg.route_scale, cfg.n_shared_experts) == \
        ("sigmoid_bias", 1.8, 1)
    assert (cfg.mtp_layers, cfg.mtp_weight, cfg.tied_head) == (1, 0.3, False)
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


# -- the latent layer -------------------------------------------------------------

def _by_hand(blk, x, cfg):
    """The latent layer's update with keys and values expanded by hand and
    the repo's plain ``full_attention``: nothing of ``_latent_attention``."""
    Bx, Sx, _ = x.shape
    H, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    pos = jnp.arange(Sx, dtype=jnp.float32)
    h = tfm._rms_norm(x, blk["ln1"], cfg.norm_eps)
    cq = tfm._rms_norm(h @ blk["wq_a"], blk["q_a_norm"], cfg.norm_eps)
    q = (cq @ blk["wq_b"]).reshape(Bx, Sx, H, dn + dr)
    a = h @ blk["wkv_a"]
    c = tfm._rms_norm(a[..., :cfg.kv_lora_rank], blk["kv_a_norm"],
                      cfg.norm_eps)
    kr = tfm._rope(a[..., None, cfg.kv_lora_rank:], cfg.rope_base, pos)
    kv = (c @ blk["wkv_b"]).reshape(Bx, Sx, H, dn + cfg.v_head_dim)
    heads_q, heads_k = [], []
    for i in range(H):                   # head by head, the naive way
        heads_q.append(jnp.concatenate(
            [q[:, :, i, :dn],
             tfm._rope(q[:, :, i:i + 1, dn:], cfg.rope_base, pos)[:, :, 0]],
            -1))
        heads_k.append(jnp.concatenate([kv[:, :, i, :dn], kr[:, :, 0]], -1))
    o = full_attention(jnp.stack(heads_q, 2), jnp.stack(heads_k, 2),
                       kv[..., dn:], causal=True)
    return x + o.reshape(Bx, Sx, -1) @ blk["wo"]


@pytest.mark.parametrize("attention", ["full", "blockwise"])
def test_latent_layer_against_full_attention_on_expanded_keys(model,
                                                              attention):
    """Forward and gradients of the latent operator against plain attention
    on keys and values expanded by hand."""
    cfg, params, _tokens, _m, _ref = model
    cfg = dataclasses.replace(cfg, attention=attention)
    blk = jax.tree.map(lambda a: a[0], params["blocks"][0])
    x = jax.random.normal(jax.random.key(7), (B, S, cfg.d_model))
    w = jax.random.normal(jax.random.key(8), x.shape)

    def prog(blk, x):
        return tfm._operator(blk, x, cfg, None, "seq", "latent")

    _close(prog(blk, x), _by_hand(blk, x, cfg))
    names = ("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo",
             "ln1")
    gp = jax.grad(lambda b, a: (prog(b, a) * w).sum(), (0, 1))(blk, x)
    gr = jax.grad(lambda b, a: (_by_hand(b, a, cfg) * w).sum(), (0, 1))(blk, x)
    _close(gp[1], gr[1], 1e-4)
    for name in names:
        _close(gp[0][name], gr[0][name], 1e-4)
        assert float(jnp.abs(gp[0][name]).max()) > 0, name


def test_one_rope_key_a_position_and_the_formulas_parameter_count(model):
    """``wkv_a`` has ``kv_lora_rank + qk_rope_dim`` columns and no other
    tensor carries rope columns for the keys; the layer's parameter count
    is the formula's — at the toy widths and at the published ones
    (21,759,232 with the two inner gains)."""
    cfg, params, _tokens, _m, _ref = model
    blk = jax.tree.map(lambda a: a[0], params["blocks"][1])
    H, d = cfg.n_heads, cfg.d_model
    want = {"wq_a": (d, 24), "q_a_norm": (24,), "wq_b": (24, H * 16),
            "wkv_a": (d, 16 + 4), "kv_a_norm": (16,),
            "wkv_b": (16, H * (12 + 16)), "wo": (H * 16, d)}
    attn = {k: v.shape for k, v in blk.items() if k in want}
    assert attn == want
    assert set(blk) == set(want) | {"ln1", "ln2", "moe", "shared_gate",
                                    "shared_up", "shared_down"}
    # the keys' rope part moves with one position's 4 columns of wkv_a, for
    # every head alike: perturb them, and every head's scores change
    x = jax.random.normal(jax.random.key(9), (1, S, d))
    bumped = dict(blk, wkv_a=blk["wkv_a"].at[:, 16:].multiply(1.5))
    run = lambda b: tfm._operator(b, x, cfg, None, "seq", "latent")
    per_head = (run(bumped) - run(blk)) @ jnp.linalg.pinv(blk["wo"])
    moved = jnp.abs(per_head.reshape(S, H, 16)[1:]).max((0, 2))
    assert float(moved.min()) > 1e-4, moved

    def count(c):
        blk = jax.eval_shape(lambda k: tfm._init_block(k, c, "latent",
                                                       "dense"),
                             jax.random.key(0))
        return sum(math.prod(blk[k].shape) for k in want)

    assert count(cfg) == sum(math.prod(s) for s in want.values())
    real = tfm.TransformerConfig(
        vocab_size=32, d_model=2048, n_layers=1, n_heads=20, d_ff=64,
        layer_ops=("latent",), layer_ffns=("dense",), q_lora_rank=768,
        kv_lora_rank=512, qk_nope_dim=192, qk_rope_dim=64, v_head_dim=256)
    assert count(real) == 1_572_864 + 3_932_160 + 1_179_648 + 4_587_520 \
        + 10_485_760 + 768 + 512 == 21_759_232


# -- the configuration's refusals ---------------------------------------------------

@pytest.mark.parametrize("kwargs,match", [
    (dict(layer_ops=("latent", "latent"), q_lora_rank=8, qk_nope_dim=6,
          qk_rope_dim=2), "kv_lora_rank, v_head_dim not set"),
    (dict(layer_ops=("latent", "latent"), **dict(LATENT, v_head_dim=12)),
     r"v_head_dim \(12\) == qk_nope_dim \+ qk_rope_dim \(12 \+ 4\)"),
    (dict(layer_ops=("latent", "latent"), **LATENT, mtp_layers=1,
          attention="blockwise", objective="block_diffusion"),
     "mtp_layers needs objective 'next_token'"),
    (dict(mtp_layers=2), "mtp_layers is 0 or 1"),
], ids=["no-ranks", "two-widths", "mtp-under-diffusion", "mtp-depth"])
def test_config_refuses_by_name(kwargs, match):
    with pytest.raises(ValueError, match=match):
        tfm.TransformerConfig(vocab_size=32, n_layers=2, **kwargs)


def test_new_fields_are_off_by_default():
    fields = tfm.TransformerConfig.__dataclass_fields__
    for name in ("q_lora_rank", "kv_lora_rank", "qk_nope_dim", "qk_rope_dim",
                 "v_head_dim", "mtp_layers"):
        assert fields[name].default == 0, name
    assert fields["mtp_weight"].default == 0.3
    cfg = tfm.TransformerConfig(vocab_size=32, n_layers=2, n_experts=4)
    params = tfm.init_params(jax.random.key(0), cfg)
    assert set(params) == {"embed", "blocks", "ln_f"}
    assert set(params["blocks"]) == {"ln1", "ln2", "wq", "wk", "wv", "wo",
                                     "moe"}


# -- the module -----------------------------------------------------------------------

def test_without_the_module_the_loss_is_todays_bit_for_bit(model):
    """``mtp_layers`` 0: the same trunk, head and loss as the stack had
    before the module existed — the main part of the module's loss, on the
    same parameters, bit for bit — and no part is returned."""
    cfg, params, tokens, _m, _ref = model
    off = dataclasses.replace(cfg, mtp_layers=0)
    trunk_only = {k: v for k, v in params.items() if k != "mtp"}
    loss0, (stats0, parts0) = tfm.lm_loss_and_stats(trunk_only, tokens, off,
                                                    aux_weight=0.0)
    assert parts0 == {}
    # today's loss, written out from the trunk
    x, _aux, _st = tfm.trunk(trunk_only, tokens, off)
    nll = tfm._token_nll(x.reshape(B * S, -1), params["head"],
                         jnp.roll(tokens, -1, 1).reshape(-1), off)
    assert float(loss0) == float(nll.reshape(B, S)[:, :-1].mean())
    loss1, (stats1, parts1) = tfm.lm_loss_and_stats(params, tokens, cfg,
                                                    aux_weight=0.0)
    assert float(parts1["main_loss"]) == float(loss0)
    assert float(loss1) == float(parts1["main_loss"]
                                 + jnp.float32(0.3) * parts1["mtp_loss"])
    # the stack's counters plus the module's
    for a, b, c in zip(stats1, stats0, parts1["mtp_stats"]):
        assert float(a) == float(b + c)          # in f32, as the sum is
    assert float(parts1["mtp_stats"].layers) == 1.0


def test_the_module_reads_the_state_before_the_final_norm(model):
    """The module's merged input is built from the last layer's output as
    ``hidden_states`` gives it (not from ``trunk``'s normed one) and the
    next token's embedding from the trunk's table."""
    cfg, params, tokens, _m, ref = model
    hs = tfm.hidden_states(params, tokens, cfg)
    assert len(hs) == 2 * cfg.n_layers + 1 + 3
    x_last = hs[2 * cfg.n_layers]
    mtp = params["mtp"]
    e = params["embed"][jnp.roll(tokens, -1, 1)]
    want = jnp.concatenate(
        [tfm._rms_norm(x_last, mtp["hnorm"], cfg.norm_eps),
         tfm._rms_norm(e, mtp["enorm"], cfg.norm_eps)], -1) @ mtp["eh_proj"]
    _close(hs[2 * cfg.n_layers + 1], want, 1e-6)
    assert mtp["eh_proj"].shape == (2 * cfg.d_model, cfg.d_model)
    err = ref.merge_error(params, x_last[0], np.asarray(tokens[0]),
                          hs[2 * cfg.n_layers + 1][0])
    assert float(err.max()) < 1e-5
    x, _aux, _stats = tfm.trunk(params, tokens, cfg)
    _close(x, tfm._rms_norm(x_last, params["ln_f"], cfg.norm_eps), 1e-6)


def test_hidden_states_are_the_trunk_and_the_module(model):
    cfg, params, tokens, _m, ref = model
    hs = tfm.hidden_states(params, tokens, cfg)
    stack = ref.halves(params)
    assert [p for p, _ in stack] == ["latent", "dense"] + ["latent", "moe"] * 4
    for i, (part, blk) in enumerate(stack):
        err, gap = ref.half_error(part, blk, hs[i][0], hs[i + 1][0])
        assert float(err.max()) < 1e-4, (i, part)
        assert gap.shape == (S,)
    z = hs[len(stack) + 1:]
    module = ref.module_halves(params)
    assert [p for p, _ in module] == ["latent", "moe"]
    for i, (part, blk) in enumerate(module):
        err, _gap = ref.half_error(part, blk, z[i][1], z[i + 1][1])
        assert float(err.max()) < 1e-4, (i, part)


def test_frozen_and_buffer_reach_the_module(model):
    """``is_buffer`` names the selection biases and ``is_frozen`` those and
    a share's routers — the module's as a layer's."""
    cfg, params, _tokens, _m, _ref = model
    paths = [p for p, _ in jax.tree_util.tree_leaves_with_path(params)]
    name = jax.tree_util.keystr
    buffers = [name(p) for p in paths if tfm.is_buffer(p)]
    frozen = [name(p) for p in paths if tfm.is_frozen(p, cfg)]
    assert buffers == ["['blocks'][1]['moe'].bias",
                       "['mtp']['block']['moe'].bias"]
    assert sorted(frozen) == sorted(buffers + [
        "['blocks'][1]['moe'].router", "['mtp']['block']['moe'].router"])
    whole = dataclasses.replace(cfg, experts_held=())
    assert [name(p) for p in paths if tfm.is_frozen(p, whole)] == buffers


@pytest.mark.parametrize("remat,chunk", [(False, 0), (True, 16)],
                         ids=["plain", "remat+chunked-loss"])
def test_both_losses_and_every_gradient(model, remat, chunk):
    """The whole objective, its two parts and every gradient of the (dense,
    moe x 4, module) stack against the reference's sequence-by-sequence,
    half-layer-by-half-layer pass through both heads."""
    cfg, params, tokens, _m, ref = model
    cfg = dataclasses.replace(cfg, remat=remat, remat_policy="full",
                              loss_chunk=chunk)
    (loss, (_stats, parts)), grads = jax.value_and_grad(
        tfm.lm_loss_and_stats, has_aux=True)(params, tokens, cfg,
                                             aux_weight=0.0)
    want, gref = ref.loss_and_grads(params, np.asarray(tokens))
    total, main, mtp = ref.losses(params, np.asarray(tokens))
    assert abs(float(loss) - want) < 1e-5 * want
    assert abs(total - want) < 1e-6 * want
    assert abs(float(parts["main_loss"]) - main) < 1e-5 * main
    assert abs(float(parts["mtp_loss"]) - mtp) < 1e-5 * mtp
    assert abs(total - (main + 0.3 * mtp)) < 1e-9
    assert set(grads) == set(gref) == {"embed", "head", "blocks", "ln_f",
                                       "mtp"}
    assert jax.tree.structure(grads) == jax.tree.structure(gref)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(grads)]
    for path, a, b in zip(paths, jax.tree.leaves(grads),
                          jax.tree.leaves(gref)):
        if "bias" in path:
            continue                     # a buffer: zero on both sides
        _close(a, b, 2e-4)
    for name in ("hnorm", "enorm", "eh_proj", "norm"):
        assert float(jnp.abs(grads["mtp"][name]).max()) > 0, name
    for name in ("wq_a", "wkv_b", "shared_down"):
        assert float(jnp.abs(grads["mtp"]["block"][name]).max()) > 0, name
    # the module's losses reach the trunk through the merge, and the
    # embedding through the next token's row: both heads' gradients add
    only_main = jax.grad(lambda p: tfm.lm_loss_and_stats(
        p, tokens, cfg, aux_weight=0.0)[1][1]["main_loss"])(params)
    assert float(jnp.abs(grads["head"] - only_main["head"]).max()) > 0
    assert float(jnp.abs(grads["blocks"][0]["wq_a"]
                         - only_main["blocks"][0]["wq_a"]).max()) > 0


def test_trainer_reports_the_losses_parts(model):
    """``Trainer.run`` with telemetry on: ``main_loss``, ``mtp_loss``,
    their share and the module's expert counters in ``train_metrics``,
    fetched with the loss; the module's router and bias left alone."""
    from swiftmpi_tpu import obs
    from swiftmpi_tpu.models.trainer import Trainer

    cfg, _params, tokens, _m, _ref = model
    was_on = obs.get_registry().enabled
    obs.set_enabled(True)
    try:
        trainer = Trainer(cfg, aux_weight=0.0, learning_rate=1e-3,
                          warmup_steps=0, decay_steps=10)
        state0 = trainer.init_state(jax.random.key(3))
        before = jax.tree.map(np.asarray, state0.params["mtp"])
        state, losses = trainer.run(state0, iter([np.asarray(tokens)] * 2))
    finally:
        obs.set_enabled(was_on)
    m = trainer.train_metrics
    mean = float(np.mean([float(x) for x in losses]))
    assert abs(m["main_loss"] + 0.3 * m["mtp_loss"] - mean) < 1e-4 * mean
    assert abs(m["mtp_loss_share"]
               - 100 * 0.3 * m["mtp_loss"] / mean) < 1e-3
    assert m["mtp_dropped_picks_per_step"] == 0.0
    assert 0.0 <= m["mtp_held_pick_share"] <= 100.0
    assert m["dropped_picks_per_step"] == 0.0
    after = state.params["mtp"]
    for name in ("router", "bias"):
        assert np.array_equal(getattr(before["block"]["moe"], name),
                              np.asarray(getattr(after["block"]["moe"], name)))
    assert not np.array_equal(before["eh_proj"], np.asarray(after["eh_proj"]))


# -- the shares add up ----------------------------------------------------------------

def test_eight_shares_and_the_shared_expert_once_are_the_whole_layer(model):
    """The routed parts the 8 chips of the deployment compute (each told
    the 8 experts it holds, each routing over all 64) plus the shared
    expert, counted once, equal the uncut expert layer of the reference."""
    cfg, _params, _tokens, m, _ref = model
    whole = dataclasses.replace(cfg, experts_held=(), mtp_layers=0,
                                layer_ops=("latent",), layer_ffns=("moe",),
                                n_layers=1)
    blk = tfm._init_block(jax.random.key(11), whole, "latent", "moe")
    full = blk["moe"]
    assert full.w_in.shape[0] == 64
    u = jax.random.normal(jax.random.key(12), (96, cfg.d_model))

    routed, picks = 0.0, 0.0
    for chip in range(8):
        lo, hi = 8 * chip, 8 * chip + 8
        share = full._replace(w_in=full.w_in[lo:hi], w_out=full.w_out[lo:hi],
                              w_gate=full.w_gate[lo:hi])
        y, _aux, stats = moe.expert_layer(
            share, u, k=cfg.moe_top_k, router=cfg.router, held=(lo, hi),
            route_scale=cfg.route_scale)
        routed, picks = routed + y, picks + float(stats.held)
        assert float(stats.dropped) == 0.0
    assert picks == 96 * 4                       # every pick on one chip
    shared = tfm._swiglu(u, blk["shared_gate"], blk["shared_up"],
                         blk["shared_down"], whole)
    with reference.highest():
        want, _gap = reference.expert_ffn(blk, u, dict(m, held=(0, 64)))
        _close(routed + shared, want)
        # and one share alone is the reference's share
        got = moe.expert_layer(
            full._replace(w_in=full.w_in[:8], w_out=full.w_out[:8],
                          w_gate=full.w_gate[:8]), u, k=4,
            router=cfg.router, held=(0, 8),
            route_scale=cfg.route_scale)[0] + shared
        _close(got, reference.expert_ffn(blk, u, dict(m, held=(0, 8)))[0])
    # the weights of a token's four picks sum to the published scale
    sel, gates, *_ = moe.route(u, full.router, full.bias, 4, cfg.router,
                               cfg.route_scale)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.8, rtol=1e-5)


# -- the phase map ----------------------------------------------------------------------

def test_everything_the_module_adds_books_under_mtp(model):
    """The compiled step's phase map: the module's layers enter scopes of
    their own (``mtp_route``, ...) that the catalog maps to ``mtp``, in the
    forward pass and in the expert loop's hand-written backward alike, so
    the stack's phases hold the stack's instructions only."""
    from swiftmpi_tpu.obs import costs
    from swiftmpi_tpu.obs.catalog import DEVICE_SCOPES, LAYER_SCOPES

    assert DEVICE_SCOPES["latent_attention"] == "latent_attention"
    assert {DEVICE_SCOPES["mtp_" + s] for s in LAYER_SCOPES} == {"mtp"}
    assert costs.phase_of("jit(f)/mtp/mtp_route/top_k") == "mtp"
    assert costs.phase_of(
        "jit(f)/transpose(jvp(mtp))/transpose(jvp(mtp_latent_attention))/dot"
    ) == "mtp"
    cfg, params, tokens, _m, _ref = model
    cfg = dataclasses.replace(cfg, remat=True, remat_policy="full")

    def scopes(c, p):
        text = jax.jit(jax.grad(lambda q: tfm.lm_loss_and_stats(
            q, tokens, c, aux_weight=0.0)[0])).lower(p).as_text(
                debug_info=True)
        names = set()
        for part in text.replace('"', "/").replace("(", "/").replace(
                ")", "/").split("/"):
            if part in DEVICE_SCOPES:
                names.add(part)
        return names

    with_module = scopes(cfg, params)
    assert {"mtp", "mtp_latent_attention", "mtp_route", "mtp_experts",
            "mtp_shared_expert", "latent_attention", "route", "experts",
            "shared_expert", "dense_ffn", "head", "embed"} <= with_module
    without = scopes(dataclasses.replace(cfg, mtp_layers=0),
                     {k: v for k, v in params.items() if k != "mtp"})
    assert not {s for s in without if s.startswith("mtp")}
