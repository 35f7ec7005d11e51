"""Plain reference of Trinity-Mini's block stack: forward pass, loss and
gradients in straightforward ``jax.numpy``, float32, matmul precision
``highest``.

No kernel, no tiles, no scan over layers, no sort, no grouped product: every
held expert is applied to every token and masked by the gate; attention is a
plain softmax over the whole key axis with the mask as a boolean array, made
from the positions a block of ``ROWS`` query rows at a time (an ``(S, S)``
mask of 16,384 positions and one head's scores under it are 1.3 GB).  It
works a *sequence*, a *half layer*, a query *head* and a block of query rows
at a time, so that the timed sizes fit beside the program's resident state;
that is its only concession to size.

Equations (``config.json`` of arcee-ai/Trinity-Mini, ``model_type afmoe``;
what the catalog's ``config`` does not carry is the family's public code and
is marked † — ``configs/trinity-mini-ep16.json`` lists each under
``assumed``)::

    x_0 = E[tok] * sqrt(d)                                   † (mup_enabled)
    h = RMSNorm(x; g1)
    q = RMSNorm_128(h W_q; gq)  32 heads x 128;  k = RMSNorm_128(h W_k; gk)  4 KV heads  †
    v = h W_v;   z = h W_g  32 x 128                          † (the gate)
    sliding layer:  q, k = RoPE(q), RoPE(k) at theta 10,000;  i sees j iff j <= i and i - j < 2048
    full layer:     no position embedding †;                  i sees j iff j <= i
    o = softmax(q k^T / sqrt(128) + M) v                      8 query heads a KV head
    x = x + RMSNorm((o * sigmoid(z)) W_o; g2)                 † (sandwich norm)
    u = RMSNorm(x; g3)
    dense layer:   y = W_down(silu(W_gate u) * W_up u)        width 6,144
    expert layer:  s = sigmoid(u W_r) over 128;  sel = top8(s + b)  (b a buffer †)
                   w = s[sel] / (sum s[sel] + 1e-20) * 2.826
                   y = shared(u) + sum_{e in sel, e held} w_e W2_e(silu(W1_e u) * W3_e u)
    x = x + RMSNorm(y; g4)                                    †
    loss = mean over positions t < S-1 of -log softmax(RMSNorm(x_L; gf) H^T)[token_{t+1}]

``held`` and the vocabulary slice are the program's: picks on experts that
are not held add nothing, the shared expert is whole, logits and loss run
over the rows of ``head`` (untied).  Departures, the same as the program's
(``configs/trinity-mini-ep16.json`` ``departures``): where a share of the
experts is held, the tokens take no gradient through the routing weights;
causal attention crosses document boundaries of a packed sequence; one block
of query rows' scores are recomputed in the backward pass so that a single
``(ROWS, S)`` matrix exists at a time; the gradient compared with the
program's is linearised at the program's own half-layer inputs
(``reference/lm.py`` says why).

The parameter tree is the program's (``models/transformer.py::init_params``
of a heterogeneous stack with an untied head): ``embed``, ``head``, ``ln_f``
and ``blocks``, a tuple of runs of equal layers stacked on a leading axis.
"""

from __future__ import annotations

import math

import numpy as np

from .bdlm import _settled, rope, update_error  # noqa: F401
from .lm import global_norm, highest, layers, mm, rms_norm  # noqa: F401

#: query rows of one head whose scores against all S keys exist at a time
ROWS = 2048
#: a layer's attention kind, from the published ``layer_types``
OPERATORS = {"sliding_attention": "sliding", "full_attention": "full"}


def dims(config: dict) -> dict:
    """What the equations need, from a configuration file's keys."""
    kinds = [(OPERATORS[config["layer_types"][i]],
              "dense" if i < config["num_dense_layers"] else "moe")
             for i in config["layers_held"]]
    return {"kinds": kinds, "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "window": int(config["sliding_window"]),
            "top_k": int(config["num_experts_per_tok"]),
            "held": tuple(config["experts_held"]),
            "scale": float(config["route_scale"])
            if config["route_norm"] else 1.0,
            "embed_scale": math.sqrt(int(config["hidden_size"]))
            if config["mup_enabled"] else 1.0}


def visible(q_pos, k_pos, window: int):
    """Whether the queries at ``q_pos`` (R,) see the keys at ``k_pos`` (S,):
    ``(R, S)`` bool; ``window`` 0 is a full layer's causal mask."""
    back = q_pos[:, None] - k_pos[None, :]
    return (back >= 0) & (back < window) if window else back >= 0


# -- the equations, one sequence (S, d) at a time ------------------------------

def attention_op(blk, x, part, m):
    """x (S, d) -> the operator's update (before the residual's norm)."""
    import jax
    import jax.numpy as jnp

    S = x.shape[0]
    H, Hkv = m["heads"], m["kv_heads"]
    D = blk["wq"].shape[1] // H
    pos = jnp.arange(S)
    h = rms_norm(x, blk["ln1"], m["eps"])
    q = rms_norm(mm(h, blk["wq"], m).reshape(S, H, D), blk["q_norm"], m["eps"])
    k = rms_norm(mm(h, blk["wk"], m).reshape(S, Hkv, D), blk["k_norm"],
                 m["eps"])
    v = mm(h, blk["wv"], m).reshape(S, Hkv, D)
    window = 0
    if part == "sliding":
        q, k = rope(q, m["theta"], pos), rope(k, m["theta"], pos)
        window = m["window"]
    rows = min(ROWS, S)

    @jax.checkpoint                     # one (rows, S) score matrix at a time
    def row_block(q_rows, q_pos, kh, vh):
        s = mm(q_rows, kh.T, m) / math.sqrt(D)
        see = visible(q_pos, pos, window)
        return mm(jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1), vh, m)

    def head(a):
        qh, kh, vh = a
        return jax.lax.map(lambda b: row_block(b[0], b[1], kh, vh),
                           (qh.reshape(S // rows, rows, D),
                            pos.reshape(S // rows, rows))).reshape(S, D)

    group = H // Hkv                    # query head i reads KV head i // group
    o = jax.lax.map(head, (q.transpose(1, 0, 2),
                           jnp.repeat(k.transpose(1, 0, 2), group, axis=0),
                           jnp.repeat(v.transpose(1, 0, 2), group, axis=0)))
    o = o.transpose(1, 0, 2).reshape(S, H * D)
    return mm(o * jax.nn.sigmoid(mm(h, blk["wg"], m)), blk["wo"], m)


def swiglu(u, w_gate, w_up, w_down, m):
    import jax

    return mm(jax.nn.silu(mm(u, w_gate, m)) * mm(u, w_up, m), w_down, m)


def router(moe, u, m):
    """(gates (S, E) with top_k non-zeros a row, the gap between the k-th
    and the (k+1)-th biased score of every token)."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(u @ moe.router)
    top, sel = jax.lax.top_k(s + moe.bias, m["top_k"] + 1)
    sel = sel[:, :m["top_k"]]
    picked = jnp.take_along_axis(s, sel, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) * m["scale"]
    gates = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], sel].set(w)
    return gates, top[:, -2] - top[:, -1]


def expert_ffn(blk, u, m):
    """The expert layer on the normed tokens ``u``: the shared expert whole
    plus the routed part the held experts give (``m["held"]``: the range
    ``blk["moe"]`` stacks) -> (y, the per-token tie gap)."""
    import jax

    moe = blk["moe"]
    lo, hi = m["held"]
    # a share of the experts: the tokens take no gradient through the
    # routing weights, whose gradient here is one chip's part of a sum
    share = hi - lo != moe.router.shape[1]
    gates, gap = router(moe, jax.lax.stop_gradient(u) if share else u, m)
    y = swiglu(u, blk["shared_gate"], blk["shared_up"], blk["shared_down"], m)
    for e in range(hi - lo):            # every held expert, every token
        y = y + gates[:, lo + e, None] * swiglu(
            u, moe.w_gate[e], moe.w_in[e], moe.w_out[e], m)
    return y, gap


def half(blk, x, part, m):
    """Half a layer on one sequence: ``part`` is an operator (``sliding``,
    ``full``) or an FFN (``dense``, ``moe``); x (S, d) -> (x + its normed
    update, the per-token tie gap: zeros unless ``moe``)."""
    import jax.numpy as jnp

    gap = jnp.zeros(x.shape[:1])
    if part in ("sliding", "full"):
        return x + rms_norm(attention_op(blk, x, part, m), blk["ln1_post"],
                            m["eps"]), gap
    u = rms_norm(x, blk["ln2"], m["eps"])
    if part == "moe":
        y, gap = expert_ffn(blk, u, m)
    else:
        y = swiglu(u, blk["w_gate"], blk["w_up"], blk["w_down"], m)
    return x + rms_norm(y, blk["ln2_post"], m["eps"]), gap


def layer(blk, x, kind, m):
    """One layer on one sequence: x (S, d) -> (S, d)."""
    op, ffn = kind
    return half(blk, half(blk, x, op, m)[0], ffn, m)[0]


def embed(params, tokens, m):
    return params["embed"][tokens] * m["embed_scale"]


def head_nll(head, ln_f, x, tokens, m):
    """Sum over the sequence's S-1 targets of the next-token negative log
    likelihood, from the last layer's output, through the untied head."""
    import jax
    import jax.numpy as jnp

    logits = mm(rms_norm(x, ln_f, m["eps"]), head.T, m)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp[:-1], tokens[1:, None], -1).sum()


# -- whole-model quantities, a sequence and a half layer at a time --------------

class Reference:
    """Jitted per-half-layer programs of one model shape, at precision
    highest.  One instance per run: compiled once per operator / FFN kind."""

    def __init__(self, m: dict):
        import jax

        self.m = m
        self.parts = [p for kind in m["kinds"] for p in kind]

        def vjp(part):
            def f(blk, x, dy):
                _, pull = jax.vjp(lambda b, a: half(b, a, part, m)[0], blk, x)
                return pull(dy)
            return jax.jit(f)

        self._half = {p: jax.jit(lambda blk, x, p=p: half(blk, x, p, m))
                      for p in set(self.parts)}
        self._vjp = {p: vjp(p) for p in set(self.parts)}
        self._head = jax.jit(jax.value_and_grad(
            lambda h, g, x, t: head_nll(h, g, x, t, m), argnums=(0, 1, 2)))
        self._nll = jax.jit(lambda h, g, x, t: head_nll(h, g, x, t, m))
        self._err = jax.jit(update_error)

    def halves(self, params) -> list:
        """[(part, its layer's parameters)] in order: 2 a layer."""
        return [(part, blk) for kind, blk in layers(params, self.m)
                for part in kind]

    def half_error(self, part, blk, x, got):
        """(per-token :func:`update_error` of the program's ``got`` for the
        input ``x`` of one sequence, per-token tie gap)."""
        with highest():
            want, gap = self._half[part](blk, x)
            return _settled((self._err(x, got, want), gap))

    def sequence_losses(self, params, tokens) -> list:
        """The mean next-token cross entropy of each sequence of ``tokens``
        (B, S) on its own, by the reference's own forward pass."""
        import jax.numpy as jnp

        out = []
        with highest():
            halves = self.halves(params)
            for seq in np.asarray(tokens):
                t = jnp.asarray(seq)
                x = embed(params, t, self.m)
                for part, blk in halves:
                    x = _settled(self._half[part](blk, x)[0])
                out.append(float(self._nll(params["head"], params["ln_f"],
                                           x, t)) / (len(seq) - 1))
        return out

    def loss(self, params, tokens) -> float:
        """Mean next-token cross entropy of ``tokens`` (B, S)."""
        return float(np.mean(self.sequence_losses(params, tokens)))

    def loss_and_grads(self, params, tokens, at=None):
        """(mean loss, gradient tree shaped like ``params``) of ``tokens``
        (B, S), a sequence and a half layer at a time: forward keeping every
        half layer's input, then back through them in reverse.  ``at`` (2L+1
        arrays (B, S, d): the program's own ``hidden_states``) linearises
        every half layer at the program's input to it
        (``reference/lm.py::Reference.loss_and_grads`` says why)."""
        import jax
        import jax.numpy as jnp

        B, S = tokens.shape
        n = B * (S - 1)
        with highest():
            halves = self.halves(params)
            g_layers = [jax.tree.map(jnp.zeros_like, blk)
                        for _kind, blk in layers(params, self.m)]
            g_embed = jnp.zeros_like(params["embed"])
            g_head = jnp.zeros_like(params["head"])
            g_lnf = jnp.zeros_like(params["ln_f"])
            total = []
            for b, seq in enumerate(np.asarray(tokens)):
                t = jnp.asarray(seq)
                if at is not None:
                    xs = [jnp.asarray(h[b]) for h in at]
                else:
                    xs = [embed(params, t, self.m)]
                    for part, blk in halves:
                        xs.append(_settled(self._half[part](blk, xs[-1])[0]))
                nll, (gh, gl, dx) = self._head(params["head"],
                                               params["ln_f"], xs[-1], t)
                total.append(nll)
                g_head, g_lnf = g_head + gh / n, g_lnf + gl / n
                dx = dx / n
                for i in reversed(range(len(halves))):
                    part, blk = halves[i]
                    gb, dx = self._vjp[part](blk, xs[i], dx)
                    g_layers[i // 2] = _settled(jax.tree.map(
                        jnp.add, g_layers[i // 2], gb))
                    xs.pop()
                g_embed = g_embed.at[t].add(dx * self.m["embed_scale"])
        blocks, k0 = [], 0
        for stacked in params["blocks"]:
            k = jax.tree.leaves(stacked)[0].shape[0]
            blocks.append(jax.tree.map(lambda *a: jnp.stack(a),
                                       *g_layers[k0:k0 + k]))
            k0 += k
        return float(sum(total)) / n, {"embed": g_embed, "head": g_head,
                                       "blocks": tuple(blocks),
                                       "ln_f": g_lnf}
