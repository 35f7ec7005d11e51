"""Nemotron-3-Nano's hybrid stack — Mamba-2 state-space mixers whose
recurrence is a chunked scan, layers that are a mixer or an FFN alone, and
ungated squared-ReLU experts beside a wider shared one — against the plain
reference (benchmark/reference/sslm.py), whose state-space layer is the
sequential recurrence over positions.

Seeded random weights at toy widths that keep every ratio of
``nemotron-3-nano-30b-a3b-ep16``: 8 heads a B/C group, a state twice a head's
width, 16 query heads a KV head, a shared expert twice an expert's width,
top-6 of 128 experts with 8 held, an untied head.  float32 operands, so
program and reference agree to rounding.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.families import sslm as family        # noqa: E402
from benchmark.lib import spec                        # noqa: E402
from benchmark.reference import sslm as reference    # noqa: E402
from swiftmpi_tpu.models import transformer as tfm    # noqa: E402
from swiftmpi_tpu.parallel import moe, ssm            # noqa: E402

CELL = "nemotron3n-ep16-8k-t8k"
B, S = 2, 64
PATTERN = [("ssm", "none"), ("none", "moe"), ("ssm", "none"), ("none", "moe"),
           ("ssm", "none"), ("full", "none"), ("none", "moe"),
           ("ssm", "none"), ("none", "moe")]
SSM = dict(ssm_heads=8, ssm_head_dim=8, ssm_state=16, ssm_groups=1)


@pytest.fixture(scope="module")
def model():
    cell = spec.load_cell(CELL, rehearse=True)
    traffic = dict(cell.traffic, sentence_tokens=S)
    cfg = dataclasses.replace(
        family.transformer_config(cell.config, traffic), remat=False)
    assert list(cfg.layer_kinds()) == PATTERN           # MEMEM*EME
    assert cfg.layer_groups() == [(kind, 1) for kind in PATTERN]
    assert cfg.ssm_heads // cfg.ssm_groups == 8
    assert cfg.ssm_state == 2 * cfg.ssm_head_dim
    assert (cfg.n_heads, cfg.kv_heads) == (16, 1)             # G = 16
    assert cfg.shared_width == 2 * cfg.d_expert
    assert (cfg.n_experts, cfg.moe_top_k, cfg.held) == \
        (128, 6, tuple(cell.config["experts_held"]))
    assert (cfg.router, cfg.route_scale, cfg.expert_gated, cfg.expert_act) \
        == ("sigmoid_bias", 2.5, False, "relu2")
    assert not cfg.tied_head
    params = tfm.init_params(jax.random.key(5), cfg)
    # every head's skip away from its start (D = 1), so that a misplaced
    # one shows
    k = iter(jax.random.split(jax.random.key(7), 4))
    params["blocks"] = tuple(
        {**g, "D": g["D"] + 0.3 * jax.random.normal(next(k), g["D"].shape)}
        if "D" in g else g for g in params["blocks"])
    tokens = jax.random.randint(jax.random.key(6), (B, S), 0, cfg.vocab_size)
    m = reference.dims(cell.config)
    return cfg, params, tokens, m, reference.Reference(m)


@pytest.fixture(scope="module")
def hidden(model):
    """``hidden_states`` of the module's model, computed once."""
    cfg, params, tokens, _m, _ref = model
    return jax.jit(lambda p, t: tfm.hidden_states(p, t, cfg))(params, tokens)


def _close(got, want, tol=2e-5, floor=1e-30):
    """Frobenius distance over ``want``'s norm (or ``floor``, where a
    quantity may be exactly zero) under ``tol``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.linalg.norm(got - want) / max(np.linalg.norm(want), floor)
    assert err < tol, err


# -- the chunked scan ---------------------------------------------------------------

def _scan_inputs(S=50, H=8, P=4, G=2, N=8, batch=2):
    k = jax.random.split(jax.random.key(0), 6)
    return (jax.random.normal(k[0], (batch, S, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (batch, S, H))),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (batch, S, G, N)),
            jax.random.normal(k[4], (batch, S, G, N)),
            jax.random.normal(k[5], (batch, S, H, P)))


@jax.jit
def _sequential(x, dt, a, b, c):
    """The reference's position-by-position recurrence, a sequence at a
    time."""
    heads = x.shape[2] // b.shape[2]
    return jnp.stack([reference.recurrence(
        x[i], dt[i], a, jnp.repeat(b[i], heads, axis=1),
        jnp.repeat(c[i], heads, axis=1), {}) for i in range(x.shape[0])])


def _chunked(chunk, **kwargs):
    return jax.jit(lambda *t: ssm.chunked_scan(*t, chunk=chunk, **kwargs))


@pytest.mark.parametrize("chunk", [16, 10, 50, 128],
                         ids=["16", "10: S no multiple", "50: one chunk",
                              "128: longer than S"])
def test_chunked_scan_against_the_sequential_recurrence(chunk):
    """Forward and every gradient (inputs, step sizes, decay rates, B, C),
    whatever the chunk: a sequence that is no multiple of it is padded
    behind its end with steps of size 0."""
    x, dt, a, b, c, w = _scan_inputs()
    with reference.highest():
        want = _sequential(x, dt, a, b, c)
        got = _chunked(chunk)(x, dt, a, b, c)
        assert got.shape == want.shape and got.dtype == jnp.float32
        _close(got, want, 1e-5)
        g_want = jax.jit(jax.grad(lambda *t: (_sequential(*t) * w).sum(),
                                  argnums=(0, 1, 2, 3, 4)))(x, dt, a, b, c)
        g_got = jax.jit(jax.grad(
            lambda *t: (ssm.chunked_scan(*t, chunk=chunk) * w).sum(),
            argnums=(0, 1, 2, 3, 4)))(x, dt, a, b, c)
    for u, v in zip(g_got, g_want):
        assert bool(jnp.isfinite(u).all())
        _close(u, v, 1e-5)
    assert ssm.n_chunks(50, chunk) == -(-50 // min(chunk, 50))


def test_two_chunk_sizes_give_one_result():
    x, dt, a, b, c, _w = _scan_inputs(S=64)
    with reference.highest():
        _close(_chunked(8)(x, dt, a, b, c), _chunked(32)(x, dt, a, b, c),
               1e-5)


def test_decays_never_overflow_however_fast():
    """``exp`` is taken of non-positive differences only: a head that
    forgets within a position (``dt a`` = -200 a step, whose running sum
    reaches -25,600 in a chunk and whose *negated* differences would
    overflow float32) gives finite outputs and gradients, equal to the
    recurrence's."""
    x, dt, a, b, c, w = _scan_inputs(S=256, H=2, P=2, G=1, N=4, batch=1)
    dt, a = jnp.full_like(dt, 2.0), jnp.array([-100.0, -1e-3])
    with reference.highest():
        got, grads = jax.jit(jax.value_and_grad(
            lambda *t: (ssm.chunked_scan(*t, chunk=128) * w).sum(),
            argnums=(0, 1, 2, 3, 4)))(x, dt, a, b, c)
        want = (_sequential(x, dt, a, b, c) * w).sum()
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want))


def test_bf16_operands_keep_decays_and_sums_in_float32():
    """With bf16 operands the products round, the decays do not: a head
    that decays by 1e-3 a position over 512 positions still reads its first
    input at exp(-0.512), which a bf16 running sum or state would lose."""
    S = 512
    x = jnp.zeros((1, S, 1, 1)).at[0, 0].set(1.0)
    dt, a = jnp.ones((1, S, 1)), jnp.array([-1e-3])
    b = c = jnp.ones((1, S, 1, 1))
    y = _chunked(128, compute_dtype=jnp.bfloat16)(x, dt, a, b, c)
    want = np.exp(-1e-3 * np.arange(1, S + 1))
    np.testing.assert_allclose(np.asarray(y[0, :, 0, 0]), want, rtol=1e-2)
    assert jax.eval_shape(ssm.chunked_scan, x, dt, a, b, c).dtype \
        == jnp.float32


def test_scan_refuses_heads_that_are_no_multiple_of_groups():
    x, dt, a, b, c, _w = _scan_inputs(H=6, G=4)
    with pytest.raises(ValueError, match="6 heads are no multiple of 4"):
        ssm.chunked_scan(x, dt, a, b, c)


# -- the configuration refuses by name ------------------------------------------------

@pytest.mark.parametrize("kwargs,match", [
    (dict(layer_ops=("ssm",)), "ssm_heads, ssm_head_dim, ssm_state, "
                               "ssm_groups not set"),
    (dict(layer_ops=("ssm",), **{**SSM, "ssm_state": 0}),
     "ssm_state not set"),
    (dict(layer_ops=("ssm",), **{**SSM, "ssm_groups": 3}),
     r"heads \(8\) must be a multiple of its groups \(3\)"),
    (dict(layer_ops=("ssm",), ssm_chunk=0, **SSM), "ssm_chunk >= 1"),
    (dict(layer_ops=("none",), layer_ffns=("none",)),
     r"layers \[0\] have neither an operator nor an FFN"),
    (dict(layer_ops=("ssm",), objective="block_diffusion",
          attention="blockwise", **SSM), "recurrence is causal"),
    (dict(expert_act="gelu"), "unknown expert_act 'gelu'"),
    (dict(layer_ffns=("half",)), "unknown layer kinds"),
])
def test_config_refuses_by_name(kwargs, match):
    with pytest.raises(ValueError, match=match):
        tfm.TransformerConfig(vocab_size=32, n_layers=1, **kwargs)


def test_new_fields_are_off_by_default():
    """A configuration that names none of the new fields builds the stack it
    built: no state-space sizes, ReLU where experts are ungated, shared
    experts of an expert's width, both halves in every layer."""
    cfg = tfm.TransformerConfig(vocab_size=32, n_experts=4,
                                n_shared_experts=2, d_expert=24)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups) \
        == (0, 0, 0, 0)
    assert (cfg.ssm_conv, cfg.ssm_chunk) == (4, 128)
    assert (cfg.expert_act, cfg.d_shared_expert) == ("relu", 0)
    assert cfg.shared_width == 48
    assert "none" not in sum(cfg.layer_kinds(), ())
    blk = tfm._init_block(jax.random.key(0), dataclasses.replace(
        cfg, expert_gated=True), "attention", "moe")
    assert {"ln1", "ln2", "shared_gate", "shared_up"} <= set(blk)
    assert blk["shared_up"].shape == (128, 48)


# -- half layers ------------------------------------------------------------------------

def test_a_half_layer_holds_its_half_alone(model):
    """One norm and one residual a layer, no parameter for the absent half;
    the counts at the toy widths are the formula's."""
    cfg, params, _tokens, _m, _ref = model
    mixer = {"ln1", "ssm_in", "ssm_out", "ssm_conv_w", "ssm_conv_b", "A_log",
             "dt_bias", "D", "ssm_norm"}
    names = {("ssm", "none"): mixer,
             ("full", "none"): {"ln1", "wq", "wk", "wv", "wo"},
             ("none", "moe"): {"ln2", "moe", "shared_up", "shared_down"}}
    for (kind, _n), g in zip(cfg.layer_groups(), params["blocks"]):
        assert set(g) == names[kind], kind
    moe_blk = params["blocks"][1]
    assert moe_blk["moe"].w_gate is None
    assert moe_blk["moe"].w_in.shape == (1, 8, 64, 24)
    assert moe_blk["shared_up"].shape == (1, 64, 48)
    g = params["blocks"][0]
    assert g["ssm_in"].shape == (1, 64, 64 + (64 + 2 * 16) + 8)
    assert g["ssm_conv_w"].shape == (1, 4, 64 + 2 * 16)
    # the published start: A in [-16, -1], softplus(dt_bias) in [1e-3, 1e-1]
    fresh = tfm.init_params(jax.random.key(5), cfg)["blocks"][0]
    a = -np.exp(np.asarray(fresh["A_log"]))
    step = np.asarray(jax.nn.softplus(fresh["dt_bias"]))
    assert (-16 <= a).all() and (a <= -1).all()
    assert (1e-3 * 0.999 <= step).all() and (step <= 1e-1 * 1.001).all()
    assert (np.asarray(fresh["D"]) == 1).all()
    for name in ("ssm_conv_w", "ssm_conv_b"):       # +-1/sqrt(4 taps)
        taps = np.asarray(fresh[name])
        assert 0.4 < np.abs(taps).max() <= 0.5 and abs(taps.mean()) < 0.1


def test_hidden_states_repeat_the_input_of_an_absent_half(model, hidden):
    cfg, params, tokens, _m, _ref = model
    hs = hidden
    assert len(hs) == 2 * cfg.n_layers + 1
    for i, (op, ffn) in enumerate(cfg.layer_kinds()):
        same = (0, 1) if op == "none" else (1, 2)
        assert ffn == "none" or op == "none"
        assert np.array_equal(hs[2 * i + same[0]], hs[2 * i + same[1]])
        assert not np.array_equal(hs[2 * i], hs[2 * i + 2])
    x, _aux, _stats = jax.jit(lambda p, t: tfm.trunk(p, t, cfg))(params,
                                                                 tokens)
    _close(x, tfm._rms_norm(hs[-1], params["ln_f"], cfg.norm_eps), 1e-6)


@pytest.fixture(scope="module")
def layer_fns(model):
    """``kind -> `` jitted (program update, reference update, program
    gradients, reference gradients) of a layer, compiled once a kind."""
    cfg, _params, _tokens, m, _ref = model
    return {kind: _layer_fns(cfg, m, kind) for kind in set(PATTERN)}


def _layer_fns(cfg, m, kind):
    part = kind[0] if kind[0] != "none" else kind[1]

    def program(blk, x):
        return tfm.block_apply(blk, x, cfg, kind=kind)[0]

    def ref(blk, x):
        return jnp.stack([reference.half(blk, x[i], part, m)[0]
                          for i in range(B)])

    def grads(f):
        return jax.jit(jax.grad(lambda blk, x, w: (f(blk, x) * w).sum(),
                                argnums=(0, 1)))

    return jax.jit(program), jax.jit(ref), grads(program), grads(ref)


@pytest.mark.parametrize("index", range(9),
                         ids=["%d:%s" % (i, op if op != "none" else ffn)
                              for i, (op, ffn) in enumerate(PATTERN)])
def test_layer_forward_and_gradient(model, hidden, layer_fns, index):
    """Each of the nine half layers against the reference's, on the
    program's own input: the update and the gradient of every parameter and
    of the input."""
    cfg, params, tokens, m, _ref = model
    kind = cfg.layer_kinds()[index]
    blk = jax.tree.map(lambda a: a[0], params["blocks"][index])
    x = hidden[2 * index]
    w = jax.random.normal(jax.random.key(9), x.shape)
    program, ref, g_program, g_ref = layer_fns[kind]
    with reference.highest():
        _close(program(blk, x) - x, ref(blk, x) - x)
        g_got, g_want = g_program(blk, x, w), g_ref(blk, x, w)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(g_got)]
    for path, a, b in zip(paths, jax.tree.leaves(g_got),
                          jax.tree.leaves(g_want)):
        if "bias" in path and "dt_bias" not in path:
            continue                     # the selection bias: a buffer
        assert float(jnp.abs(b).max()) > 0, path
        _close(a, b, 1e-4)


@pytest.mark.parametrize("remat,chunk", [(False, 0), (True, 16)],
                         ids=["plain", "remat+chunked-loss"])
def test_whole_loss_and_gradient(model, remat, chunk):
    cfg, params, tokens, _m, ref = model
    cfg = dataclasses.replace(cfg, remat=remat, remat_policy="full",
                              loss_chunk=chunk)
    (loss, (_stats, parts)), grads = jax.jit(jax.value_and_grad(
        lambda p, t: tfm.lm_loss_and_stats(p, t, cfg, aux_weight=0.0),
        has_aux=True))(params, tokens)
    want, gref = ref.loss_and_grads(params, np.asarray(tokens))
    assert abs(float(loss) - want) < 1e-5 * want
    assert abs(ref.loss(params, np.asarray(tokens)) - want) < 1e-6 * want
    # sequences x chunks a sequence x state-space layers
    assert float(parts["ssm_scan_chunks"]) == B * (S // cfg.ssm_chunk) * 4
    assert jax.tree.structure(grads) == jax.tree.structure(gref)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(grads)]
    for path, a, b in zip(paths, jax.tree.leaves(grads),
                          jax.tree.leaves(gref)):
        if ".bias" in path:
            continue                     # a buffer: zero on both sides
        _close(a, b, 2e-4)


# -- squared-ReLU experts ----------------------------------------------------------------

@pytest.mark.parametrize("act", sorted(moe.ACTIVATIONS))
def test_ungated_experts_through_the_hand_written_backward(act):
    """``expert_layer`` (the sorted walk and ``_grouped``'s hand-written
    backward) against ``jax.grad`` of ``moe_ffn_reference``, for every
    ungated activation: the output and the gradient of every weight, of the
    tokens and of the router."""
    E, d, f, T, k = 8, 16, 24, 40, 3
    p = moe.init_moe_params(jax.random.key(1), d, f, E)
    assert p.w_gate is None
    x = jax.random.normal(jax.random.key(2), (T, d))
    w = jax.random.normal(jax.random.key(3), (T, d))

    def layer(p, x):
        return (moe.expert_layer(p, x, k=k, act=act, row_chunk=16)[0]
                * w).sum()

    def golden(p, x):
        return (moe.moe_ffn_reference(p, x, k=k, act=act)[0] * w).sum()

    with reference.highest():
        got = jax.jit(jax.value_and_grad(layer, argnums=(0, 1)))(p, x)
        want = jax.jit(jax.value_and_grad(golden, argnums=(0, 1)))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.abs(b).max()) > 0
        _close(a, b, 1e-4)
    # the square is in it: the two activations differ
    other = "relu" if act == "relu2" else "relu2"
    assert abs(float(got[0]) - float(jax.jit(
        lambda p, x: (moe.moe_ffn_reference(p, x, k=k, act=other)[0]
                      * w).sum())(p, x))) > 1e-3
    with pytest.raises(ValueError, match="unknown expert activation"):
        moe.expert_layer(p, x, k=k, act="gelu")


# -- the shares add up ----------------------------------------------------------------

def test_sixteen_shares_and_the_shared_expert_once_are_the_whole_layer(model):
    """The routed parts the 16 chips of the deployment compute (each told
    the 8 experts it holds, each routing over all 128) plus the shared
    expert, counted once, equal the uncut expert layer of the reference."""
    cfg, _params, _tokens, m, _ref = model
    whole = dataclasses.replace(cfg, experts_held=(), n_layers=1,
                                layer_ops=("none",), layer_ffns=("moe",))
    blk = tfm._init_block(jax.random.key(11), whole, "none", "moe")
    full = blk["moe"]
    assert full.w_in.shape[0] == 128 and full.w_gate is None
    u = jax.random.normal(jax.random.key(12), (96, cfg.d_model))
    kwargs = dict(k=cfg.moe_top_k, router=cfg.router,
                  route_scale=cfg.route_scale, act=cfg.expert_act)

    @jax.jit
    def chips(full, u):
        """(the 16 chips' routed parts summed, held picks, dropped picks)."""
        routed, held, dropped = 0.0, 0.0, 0.0
        for chip in range(16):
            lo, hi = 8 * chip, 8 * chip + 8
            share = full._replace(w_in=full.w_in[lo:hi],
                                  w_out=full.w_out[lo:hi])
            y, _aux, stats = moe.expert_layer(share, u, held=(lo, hi),
                                              **kwargs)
            routed, held = routed + y, held + stats.held
            dropped = dropped + stats.dropped
        return routed, held, dropped

    with reference.highest():
        routed, picks, dropped = chips(full, u)
    assert float(picks) == 96 * 6                # every pick on one chip
    assert float(dropped) == 0.0
    shared = tfm._shared_expert(blk, u, whole)
    with reference.highest():
        want, _gap = reference.expert_ffn(blk, u, dict(m, held=(0, 128)))
        _close(routed + shared, want)
        # and one share alone is the reference's share
        got = moe.expert_layer(
            full._replace(w_in=full.w_in[:8], w_out=full.w_out[:8]), u,
            held=(0, 8), **kwargs)[0] + shared
        _close(got, reference.expert_ffn(blk, u, dict(m, held=(0, 8)))[0])
    # the weights of a token's six picks sum to the published scale
    _sel, gates, *_ = moe.route(u, full.router, full.bias, 6, cfg.router,
                                cfg.route_scale)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-5)


# -- the trainer: counters and scopes ------------------------------------------------------

def test_trainer_counts_the_scans_chunks_and_books_their_scopes(model):
    """``Trainer.run`` with telemetry on: ``ssm_scan_chunks`` beside the
    expert counters in ``train_metrics``, fetched with the loss; the
    compiled step's phase map books the mixers under ``ssm_mixer`` and the
    recurrence under ``ssm_scan``; a share's routers and biases stay."""
    from swiftmpi_tpu import obs
    from swiftmpi_tpu.models.trainer import Trainer
    from swiftmpi_tpu.obs.catalog import DEVICE_SCOPES

    cfg, _params, tokens, _m, _ref = model
    was_on = obs.get_registry().enabled
    obs.set_enabled(True)
    try:
        trainer = Trainer(cfg, aux_weight=0.0, learning_rate=1e-3,
                          warmup_steps=0, decay_steps=10)
        state0 = trainer.init_state(jax.random.key(3))
        before = jax.tree.map(np.asarray, state0.params["blocks"])
        state, losses = trainer.run(state0, iter([np.asarray(tokens)] * 2))
        pm = obs.costs.phase_map("trainer_step")
    finally:
        obs.set_enabled(was_on)
    m = trainer.train_metrics
    assert m["steps"] == 2 and len(losses) == 2
    assert m["ssm_scan_chunks"] == B * (S // cfg.ssm_chunk) * 4
    assert m["dropped_picks_per_step"] == 0.0
    assert 0.0 < m["held_pick_share"] < 100.0
    assert "main_loss" not in m
    phases = set(pm["phase"].values())
    assert {"ssm_mixer", "ssm_scan", "attention", "route", "experts",
            "shared_expert", "embed", "head", "optimizer"} <= phases
    assert phases <= set(DEVICE_SCOPES.values()) | {"unscoped"}
    for g0, g1 in zip(before, state.params["blocks"]):
        if "moe" in g0:
            for name in ("router", "bias"):
                assert np.array_equal(getattr(g0["moe"], name),
                                      np.asarray(getattr(g1["moe"], name)))
        else:
            moved = "ssm_in" if "ssm_in" in g0 else "wq"
            assert not np.array_equal(g0[moved], np.asarray(g1[moved]))
    for name in ("A_log", "dt_bias", "D", "ssm_conv_w", "ssm_conv_b"):
        assert not np.array_equal(before[0][name],
                                  np.asarray(state.params["blocks"][0][name]))
