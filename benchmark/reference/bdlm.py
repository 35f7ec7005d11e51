"""Plain reference of SDAR's block stack trained by block diffusion: forward
pass, loss and gradients in straightforward ``jax.numpy``, float32, matmul
precision ``highest``.

No kernel, no tiles, no sort, no grouped product: every held expert is
applied to every token and masked by the gate; attention is a plain softmax
over the whole ``2S`` key axis with the mask as a boolean array.  It works a
*sequence*, a *layer*, a query *head* and a block of ``ROWS`` query rows at a
time, so that the timed sizes fit beside the program's resident state; that
is its only concession to size.

Equations (``config.json`` of JetLM/SDAR-30B-A3B-Chat, ``model_type
sdar_moe``, derived from Qwen3-MoE; every layer alike)::

    u = RMSNorm(x)
    q = RoPE_p(RMSNorm_128(u W_q))  32 heads x 128;  k = RoPE_p(RMSNorm_128(u W_k))  4 KV heads;  v = u W_v
    h = x + concat_heads(softmax(q k^T / sqrt(128) + M) v) W_o      8 query heads a KV head
    w = RMSNorm(h);  s = softmax(w W_r) over 128;  sel = top8(s);  g = s[sel] / sum s[sel]
    y = h + sum_{e in sel, e held} g_e W2_e(silu(W1_e w) * W3_e w)
    logits = RMSNorm(y_L) W_head^T                                  W_head (V, d), not the embedding

Block diffusion (BD3-LM's objective): blocks of ``Lb`` positions, ``b(i) = i
// Lb``; a sequence draws one ``u ~ U[0, 1)`` and block ``b`` gets ``t_b = eps
+ (1 - eps) ((u + b / Nb) mod 1)``; ``m_i ~ Bernoulli(t_b(i))``; ``xt_i =
MASK if m_i else x0_i``.  The stack runs ``z = [xt ; x0]`` with position ids
``p = [0..S-1 ; 0..S-1]``, and query ``a`` sees key ``c`` iff
(:func:`visible_matrix`)::

    a noisy, c noisy:  b(a) == b(c)              a noisy, c clean:  b(c - S) <  b(a)
    a clean, c clean:  b(c - S) <= b(a - S)      a clean, c noisy:  never

    loss = (1 / (B S)) sum over noisy positions i of  m_i / t_b(i) * -log softmax(logits_i)[x0_i]

``held`` and the vocabulary slice are the program's: picks on experts that
are not held add nothing, logits and loss run over the rows of ``head``.
Departures, the same as the program's (``configs/sdar-30b-a3b-ep8.json``
``departures``): where a share of the experts is held, the tokens take no
gradient through the routing weights; one block of query rows' scores are
recomputed in the backward pass so that a single ``(ROWS, 2S)`` matrix exists
at a time; the gradient compared with the program's is linearised at the
program's own half-layer inputs (``reference/lm.py`` says why).

The parameter tree is the program's (``models/transformer.py::init_params``
of a heterogeneous stack with an untied head): ``embed``, ``head``, ``ln_f``
and ``blocks``, a tuple of runs of equal layers stacked on a leading axis.
"""

from __future__ import annotations

import math

import numpy as np

from .lm import global_norm, highest, layers, mm, rms_norm  # noqa: F401

#: query rows of one head whose scores against all 2S keys exist at a time
ROWS = 2048


def dims(config: dict) -> dict:
    """What the equations need, from a configuration file's keys."""
    d = config["diffusion"]
    return {"kinds": [("attention", "moe")] * int(config["num_hidden_layers"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "top_k": int(config["num_experts_per_tok"]),
            "held": tuple(config["experts_held"]),
            "block": int(d["block_length"]),
            "mask_token": int(d["mask_token_id"]),
            "noise_eps": float(d["noise_eps"])}


# -- the objective's noise and mask --------------------------------------------

def noise(key, tokens, m: dict):
    """The law above, drawn from a JAX key: ``(noisy (B, S), weights (B, S)
    = m / t)``.  Written from the equations; ``tests/test_sdar.py`` and every
    run's first-step check hold it equal to the program's draw from the
    same key."""
    import jax
    import jax.numpy as jnp

    B, S = tokens.shape
    n_blocks = S // m["block"]
    key_u, key_m = jax.random.split(key)
    u = jax.random.uniform(key_u, (B, 1))
    b = jnp.arange(S) // m["block"]
    t = m["noise_eps"] + (1.0 - m["noise_eps"]) * jnp.mod(
        u + b.astype(jnp.float32) / n_blocks, 1.0)
    masked = jax.random.uniform(key_m, (B, S)) < t
    return (jnp.where(masked, m["mask_token"], tokens).astype(jnp.int32),
            jnp.where(masked, 1.0 / t, 0.0))


def visible_matrix(S: int, Lb: int) -> np.ndarray:
    """(2S, 2S) bool: row ``a`` (a query) sees column ``c`` (a key)."""
    i = np.arange(2 * S)
    noisy, b = i < S, (i % S) // Lb
    qn, kn = noisy[:, None], noisy[None, :]
    bq, bk = b[:, None], b[None, :]
    return np.where(kn, qn & (bq == bk), np.where(qn, bk < bq, bk <= bq))


# -- the equations, one sequence's z (2S, d) at a time ---------------------------

def rope(x, theta, positions):
    """x (P, H, D) at position ids ``positions`` (P,): rotate-half pairing,
    inv_freq = theta^(-2i/D)."""
    import jax.numpy as jnp

    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + rot * sin


def attention_op(blk, x, mask, m):
    """x (P, d) with P = 2S, mask (P, P) bool -> the operator's update."""
    import jax
    import jax.numpy as jnp

    P = x.shape[0]
    H, Hkv = m["heads"], m["kv_heads"]
    D = blk["wq"].shape[1] // H
    pos = jnp.tile(jnp.arange(P // 2), 2)
    u = rms_norm(x, blk["ln1"], m["eps"])
    q = rope(rms_norm(mm(u, blk["wq"], m).reshape(P, H, D), blk["q_norm"],
                      m["eps"]), m["theta"], pos)
    k = rope(rms_norm(mm(u, blk["wk"], m).reshape(P, Hkv, D), blk["k_norm"],
                      m["eps"]), m["theta"], pos)
    v = mm(u, blk["wv"], m).reshape(P, Hkv, D)
    rows = min(ROWS, P)
    mask_rows = mask.reshape(P // rows, rows, P)

    @jax.checkpoint                     # one (rows, P) score matrix at a time
    def row_block(q_rows, see, kh, vh):
        s = mm(q_rows, kh.T, m) / math.sqrt(D)
        return mm(jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1), vh, m)

    def head(a):
        qh, kh, vh = a
        return jax.lax.map(lambda b: row_block(b[0], b[1], kh, vh),
                           (qh.reshape(P // rows, rows, D), mask_rows)
                           ).reshape(P, D)

    group = H // Hkv                    # query head i reads KV head i // group
    o = jax.lax.map(head, (q.transpose(1, 0, 2),
                           jnp.repeat(k.transpose(1, 0, 2), group, axis=0),
                           jnp.repeat(v.transpose(1, 0, 2), group, axis=0)))
    return mm(o.transpose(1, 0, 2).reshape(P, H * D), blk["wo"], m)


def router(moe, w, m):
    """(gates (P, E) with top_k non-zeros a row, the gap between the k-th
    and the (k+1)-th score of every token)."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.softmax(w @ moe.router, axis=-1)
    top, sel = jax.lax.top_k(s, m["top_k"] + 1)
    g = top[:, :-1] / top[:, :-1].sum(-1, keepdims=True)
    gates = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None],
                                 sel[:, :-1]].set(g)
    return gates, top[:, -2] - top[:, -1]


def expert_ffn(blk, x, m):
    """-> (the FFN's update, the per-token tie gap)."""
    import jax

    moe = blk["moe"]
    w = rms_norm(x, blk["ln2"], m["eps"])
    lo, hi = m["held"]
    # a share of the experts: the tokens take no gradient through the
    # routing weights, whose gradient here is one chip's part of a sum
    share = hi - lo != moe.router.shape[1]
    gates, gap = router(moe, jax.lax.stop_gradient(w) if share else w, m)
    y = 0.0
    for e in range(hi - lo):            # every held expert, every token
        out = mm(jax.nn.silu(mm(w, moe.w_gate[e], m))
                 * mm(w, moe.w_in[e], m), moe.w_out[e], m)
        y = y + gates[:, lo + e, None] * out
    return y, gap


def half(blk, x, mask, part, m):
    """Half a layer on one sequence's z: ``part`` is ``attention`` or
    ``moe``; x (2S, d) -> (x + its update, the per-token tie gap: zeros
    unless ``moe``)."""
    import jax.numpy as jnp

    if part == "moe":
        y, gap = expert_ffn(blk, x, m)
        return x + y, gap
    return x + attention_op(blk, x, mask, m), jnp.zeros(x.shape[:1])


def layer(blk, x, mask, m):
    """One layer on one sequence's z (2S, d)."""
    return half(blk, half(blk, x, mask, "attention", m)[0], mask, "moe",
                m)[0]


def head_nll(head, ln_f, x, tokens, weights, m):
    """Weighted sum over one sequence's noisy half of the negative log
    likelihood of each position's own token: x (2S, d) the last layer's
    output, tokens / weights (S,)."""
    import jax
    import jax.numpy as jnp

    S = tokens.shape[0]
    logits = mm(rms_norm(x[:S], ln_f, m["eps"]), head.T, m)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -(weights * jnp.take_along_axis(logp, tokens[:, None], -1)[:, 0]
             ).sum()


# -- whole-model quantities, a sequence and a half layer at a time --------------

def _settled(tree):
    """``tree`` once the device has computed it.  JAX hands out a result
    before it exists and allocates it at once, so a host loop that runs
    ahead of the device holds as many 134 MB half-layer inputs and outputs
    as it got ahead by: the memory in use then follows the host's timing
    (one run in ten read 128 MiB less at its peak).  Waiting here costs
    nothing, the device being the slower of the two."""
    import jax

    return jax.block_until_ready(tree)


def update_error(x, got, want):
    """Per token of one sequence, the distance of the program's residual
    update ``got - x`` from the reference's ``want - x``, over the larger
    of that token's own reference update norm and the root mean square of
    the sequence's.  ``reference/lm.py`` divides by the root mean square
    alone (a token's own norm can be near zero where an average cancels),
    which reads a share's expert layer wrongly: in a layer whose held
    experts most tokens do not pick, most updates are exactly zero, the
    root mean square is far below the update of the few tokens that do
    pick them (25.7 x in one sequence of one layer), and their ordinary
    bf16 error (3.8e-3 of their own update) reads 25.7 times as large
    (PERF.md section 6, PR 33).  f32, on the device."""
    import jax.numpy as jnp

    err = jnp.linalg.norm(got - want, axis=-1)
    size = jnp.linalg.norm(want - x, axis=-1)
    return err / jnp.maximum(size, jnp.sqrt(jnp.mean(size * size) + 1e-30))


class Reference:
    """Jitted per-half-layer programs of one model shape, at precision
    highest.  One instance per run: compiled once per part."""

    def __init__(self, m: dict):
        import jax

        self.m = m
        self.parts = [p for kind in m["kinds"] for p in kind]
        self._masks = {}

        def vjp(part):
            def f(blk, x, mask, dy):
                _, pull = jax.vjp(lambda b, a: half(b, a, mask, part, m)[0],
                                  blk, x)
                return pull(dy)
            return jax.jit(f)

        self._half = {p: jax.jit(lambda blk, x, mask, p=p:
                                 half(blk, x, mask, p, m))
                      for p in set(self.parts)}
        self._vjp = {p: vjp(p) for p in set(self.parts)}
        self._head = jax.jit(jax.value_and_grad(
            lambda h, g, x, t, w: head_nll(h, g, x, t, w, m),
            argnums=(0, 1, 2)))
        self._nll = jax.jit(lambda h, g, x, t, w: head_nll(h, g, x, t, w, m))
        self._err = jax.jit(update_error)

    def mask(self, S: int):
        """The (2S, 2S) boolean mask, on the device, made once a length."""
        import jax.numpy as jnp

        if S not in self._masks:
            self._masks[S] = jnp.asarray(visible_matrix(S, self.m["block"]))
        return self._masks[S]

    def halves(self, params) -> list:
        """[(part, its layer's parameters)] in order: 2 a layer."""
        return [(part, blk) for kind, blk in layers(params, self.m)
                for part in kind]

    def half_error(self, part, blk, x, got):
        """(per-token :func:`update_error` of the program's ``got`` for the
        input ``x`` (2S, d) of one sequence, per-token tie gap)."""
        with highest():
            want, gap = self._half[part](blk, x, self.mask(x.shape[0] // 2))
            return _settled((self._err(x, got, want), gap))

    def _inputs(self, params, noisy, tokens):
        import jax.numpy as jnp

        return params["embed"][jnp.concatenate([jnp.asarray(noisy),
                                                jnp.asarray(tokens)])]

    def sequence_losses(self, params, tokens, noisy, weights) -> list:
        """The loss of each sequence of ``tokens`` (B, S) on its own (its
        weighted sum / S), by the reference's own forward pass."""
        import jax.numpy as jnp

        S = tokens.shape[1]
        out = []
        with highest():
            halves = self.halves(params)
            for t, n, w in zip(*(np.asarray(a) for a in
                                 (tokens, noisy, weights))):
                x = self._inputs(params, n, t)
                for part, blk in halves:
                    x = _settled(self._half[part](blk, x, self.mask(S))[0])
                out.append(float(self._nll(params["head"], params["ln_f"], x,
                                           jnp.asarray(t), jnp.asarray(w)))
                           / S)
        return out

    def loss(self, params, tokens, noisy, weights) -> float:
        """The block-diffusion loss of ``tokens`` (B, S) under the draw
        ``(noisy, weights)``."""
        return float(np.mean(self.sequence_losses(params, tokens, noisy,
                                                  weights)))

    def loss_and_grads(self, params, tokens, noisy, weights, at=None):
        """(loss, gradient tree shaped like ``params``), a sequence and a
        half layer at a time: forward keeping every half layer's input, then
        back through them in reverse.  ``at`` (2L+1 arrays (B, 2S, d): the
        program's own ``hidden_states`` of ``[noisy ; tokens]``) linearises
        every half layer at the program's input to it
        (``reference/lm.py::Reference.loss_and_grads`` says why)."""
        import jax
        import jax.numpy as jnp

        B, S = tokens.shape
        n = B * S
        with highest():
            halves = self.halves(params)
            g_layers = [jax.tree.map(jnp.zeros_like, blk)
                        for _kind, blk in layers(params, self.m)]
            g_embed = jnp.zeros_like(params["embed"])
            g_head = jnp.zeros_like(params["head"])
            g_lnf = jnp.zeros_like(params["ln_f"])
            total = []
            for b in range(B):
                t, w = (jnp.asarray(np.asarray(a)[b])
                        for a in (tokens, weights))
                if at is not None:
                    xs = [jnp.asarray(h[b]) for h in at]
                else:
                    xs = [self._inputs(params, np.asarray(noisy)[b], t)]
                    for part, blk in halves:
                        xs.append(_settled(self._half[part](
                            blk, xs[-1], self.mask(S))[0]))
                nll, (gh, gl, dx) = self._head(params["head"],
                                               params["ln_f"], xs[-1], t, w)
                total.append(nll)
                g_head, g_lnf = g_head + gh / n, g_lnf + gl / n
                dx = dx / n
                for i in reversed(range(len(halves))):
                    part, blk = halves[i]
                    gb, dx = self._vjp[part](blk, xs[i], self.mask(S), dx)
                    g_layers[i // 2] = _settled(jax.tree.map(
                        jnp.add, g_layers[i // 2], gb))
                    xs.pop()
                z = jnp.concatenate([jnp.asarray(np.asarray(noisy)[b]), t])
                g_embed = g_embed.at[z].add(dx)
        blocks, k0 = [], 0
        for stacked in params["blocks"]:
            k = jax.tree.leaves(stacked)[0].shape[0]
            blocks.append(jax.tree.map(lambda *a: jnp.stack(a),
                                       *g_layers[k0:k0 + k]))
            k0 += k
        return float(sum(total)) / n, {"embed": g_embed, "head": g_head,
                                       "blocks": tuple(blocks),
                                       "ln_f": g_lnf}
