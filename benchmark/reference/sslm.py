"""Plain reference of Nemotron-3-Nano's hybrid stack: forward pass, loss and
gradients in straightforward ``jax.numpy``, float32, matmul precision
``highest``.

No kernel, no chunks, no tiles, no scan over layers, no sort, no grouped
product: the state-space layer is the **sequential recurrence**, one position
after another over each head's ``(P, N)`` state (nothing of the program's
chunk algebra: no running sums, no ``(Q, Q)`` forms, no chunk states); the
convolution is four shifted adds; every held expert is applied to every token
and masked by the gate; attention is a plain softmax over the whole key axis
with the causal mask as a boolean array.  It works a *sequence*, a *layer*, a
query *head* and a block of query rows at a time, and the recurrence's
backward pass recomputes ``BLOCK`` positions' states at a time from the state
that entered them (all 8,192 positions' 64 x 64 x 128 states are 17 GB), so
that the timed sizes fit beside the program's resident state; that is its
only concession to size.

Equations (``config.json`` of nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
``model_type nemotron_h``; Mamba-2 as arXiv 2405.21060 gives it; what the
catalog's ``config`` does not carry is marked † and listed under ``assumed``
in ``configs/nemotron-3-nano-30b-a3b-ep16.json``).  Every layer is **one**
of three, ``x <- x + f(RMSNorm(x; g))`` with eps 1e-5::

    M  [z | xBC | dt] = u W_in                     4,096 | 6,144 | 64, no bias
       xBC = silu(conv1d_causal(xBC; w, b))        depthwise, 4 taps, bias
       [x | B | C] = xBC                           64 heads x 64 | 8 groups x 128, twice
       D_t = softplus(dt_t + dt_bias);  A = -exp(A_log)              a head
       s_t = exp(D_t A) s_{t-1} + D_t x_t (x) B_t                    s_0 = 0, (64, 128) a head
       y_t = s_t C_t + D x_t                       B, C of the head's group (8 heads each)
       f = RMSNorm_groups_of_512(y * silu(z); gain) W_out            4,096 -> 2,688
    E  s = sigmoid(u W_r) over 128;  sel = top6(s + b)  (b a buffer †)
       w = s[sel] / (sum s[sel] + 1e-20) * 2.5
       f = relu(u W_up^s)^2 W_down^s + sum_{e in sel, e held} w_e relu(u W_up_e)^2 W_down_e
    *  q = u W_q  32 heads x 128;  k = u W_k, v = u W_v  2 KV heads;  no position embedding †
       f = softmax(q k^T / sqrt(128) + causal) v W_o                 16 query heads a KV head
    loss = mean over positions t < S-1 of -log softmax(RMSNorm(x_L; gf) H^T)[token_{t+1}]

``held`` and the vocabulary slice are the program's: picks on experts that
are not held add nothing, the shared expert is whole, logits and loss run
over the rows of ``head`` (untied).  Departures, the same as the program's
(the configuration's ``departures``): where a share of the experts is held,
the tokens take no gradient through the routing weights; attention and the
recurrence run across document boundaries of a packed sequence; the gradient
compared with the program's is linearised at the program's own half-layer
inputs (``reference/lm.py`` says why).

The parameter tree is the program's (``models/transformer.py::init_params``
of a heterogeneous stack with an untied head): ``embed``, ``head``, ``ln_f``
and ``blocks``, a tuple of runs of equal layers stacked on a leading axis; a
layer holds its one half's parameters (``ln1`` and the operator's, or ``ln2``
and the expert layer's).
"""

from __future__ import annotations

import math

import numpy as np

from .bdlm import _settled, update_error  # noqa: F401
from .lm import highest, layers, mm, rms_norm  # noqa: F401
from .mlalm import global_norm  # noqa: F401
from .swlm import head_nll, router, visible  # noqa: F401

#: query rows of one head whose scores against all S keys exist at a time
ROWS = 2048
#: positions of the recurrence whose states exist at a time in its backward
BLOCK = 128
#: a layer's one half, from a letter of the published pattern
PATTERN = {"M": ("ssm", "none"), "E": ("none", "moe"), "*": ("full", "none")}


def dims(config: dict) -> dict:
    """What the equations need, from a configuration file's keys."""
    return {"kinds": [PATTERN[config["hybrid_override_pattern"][i]]
                      for i in config["layers_held"]],
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "eps": float(config["layer_norm_epsilon"]),
            "top_k": int(config["num_experts_per_tok"]),
            "held": tuple(config["experts_held"]),
            "scale": float(config["routed_scaling_factor"])
            if config["norm_topk_prob"] else 1.0,
            "ssm_heads": int(config["mamba_num_heads"]),
            "ssm_head_dim": int(config["mamba_head_dim"]),
            "ssm_state": int(config["ssm_state_size"]),
            "ssm_groups": int(config["n_groups"]),
            "kernel": int(config["conv_kernel"])}


# -- the equations, one sequence (S, d) at a time ------------------------------

def _rounded(t, m, key="operands"):
    """``t`` as ``m[key]`` would hold it (absent in the reference proper):
    the reference *as a lower precision would compute it*
    (``tools/sslm_lower_precision.py``)."""
    import jax
    import jax.numpy as jnp

    if m.get(key) is None:
        return t
    # not ``astype`` there and back: the TPU compiler is allowed to keep the
    # excess precision of such a pair inside a fused loop body, and did (the
    # recurrence "in bfloat16" read 0.0 against itself on the chip, PR 46)
    kind = jnp.finfo(m[key])
    return jax.lax.reduce_precision(t, kind.nexp, kind.nmant)


def recurrence(xs, dt, a, b, c, m):
    """``s_t = exp(dt_t a) s_{t-1} + dt_t x_t (x) b_t``, ``y_t = s_t c_t``
    from ``s_0 = 0``, position by position: xs (S, H, P), dt (S, H), a (H,),
    b and c (S, H, N) -> y (S, H, P).  ``m["ssm_dtype"]`` (absent in the
    reference proper) holds each step's decay and state in that dtype."""
    import jax
    import jax.numpy as jnp

    S, H, P = xs.shape

    def step(s, t):
        x_t, dt_t, b_t, c_t = t
        decay = _rounded(jnp.exp(dt_t * a), m, "ssm_dtype")
        s = decay[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        s = _rounded(s, m, "ssm_dtype")
        return s, (s * c_t[:, None, :]).sum(-1)

    @jax.checkpoint                     # BLOCK positions' states at a time
    def block(s, ts):
        return jax.lax.scan(step, s, ts)

    rows = BLOCK if S % BLOCK == 0 else S
    _, y = jax.lax.scan(
        block, jnp.zeros((H, P, b.shape[-1]), jnp.float32),
        jax.tree.map(lambda t: t.reshape(S // rows, rows, *t.shape[1:]),
                     (_rounded(xs, m), dt, _rounded(b, m), _rounded(c, m))))
    return y.reshape(S, H, P)


def ssm_op(blk, x, m):
    """x (S, d) -> the Mamba-2 mixer's update."""
    import jax
    import jax.numpy as jnp

    S = x.shape[0]
    H, P, N, G = (m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"],
                  m["ssm_groups"])
    inner, K = H * P, m["kernel"]
    u = rms_norm(x, blk["ln1"], m["eps"])
    proj = mm(u, blk["ssm_in"], m)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * G * N],
                  proj[:, 2 * inner + 2 * G * N:])
    conv = jnp.zeros_like(xbc) + blk["ssm_conv_b"]
    for j in range(K):                  # tap j reads position t - (K-1) + j
        back = K - 1 - j
        shifted = xbc if back == 0 else jnp.concatenate(
            [jnp.zeros_like(xbc[:back]), xbc[:-back]], 0)
        conv = conv + blk["ssm_conv_w"][j] * shifted
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :inner].reshape(S, H, P)
    b = xbc[:, inner:inner + G * N].reshape(S, G, N)
    c = xbc[:, inner + G * N:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + blk["dt_bias"])
    y = recurrence(xs, dt, -jnp.exp(blk["A_log"]),
                   jnp.repeat(b, H // G, axis=1),     # head h reads group
                   jnp.repeat(c, H // G, axis=1), m)  # h // (H / G)
    y = (y + blk["D"][:, None] * xs).reshape(S, inner) * jax.nn.silu(z)
    y = y.reshape(S, G, inner // G)
    y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + m["eps"])
    return mm(y.reshape(S, inner) * blk["ssm_norm"], blk["ssm_out"], m)


def attention_op(blk, x, m):
    """x (S, d) -> causal attention's update: no position embedding, no
    head norms, no gate."""
    import jax
    import jax.numpy as jnp

    S = x.shape[0]
    H, Hkv = m["heads"], m["kv_heads"]
    D = blk["wq"].shape[1] // H
    pos = jnp.arange(S)
    u = rms_norm(x, blk["ln1"], m["eps"])
    q = mm(u, blk["wq"], m).reshape(S, H, D)
    k = mm(u, blk["wk"], m).reshape(S, Hkv, D)
    v = mm(u, blk["wv"], m).reshape(S, Hkv, D)
    rows = min(ROWS, S)

    @jax.checkpoint                     # one (rows, S) score matrix at a time
    def row_block(q_rows, q_pos, kh, vh):
        s = mm(q_rows, kh.T, m) / math.sqrt(D)
        see = visible(q_pos, pos, 0)
        return mm(jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1), vh, m)

    def head(a):
        qh, kh, vh = a
        return jax.lax.map(lambda r: row_block(r[0], r[1], kh, vh),
                           (qh.reshape(S // rows, rows, D),
                            pos.reshape(S // rows, rows))).reshape(S, D)

    group = H // Hkv                    # query head i reads KV head i // group
    o = jax.lax.map(head, (q.transpose(1, 0, 2),
                           jnp.repeat(k.transpose(1, 0, 2), group, axis=0),
                           jnp.repeat(v.transpose(1, 0, 2), group, axis=0)))
    return mm(o.transpose(1, 0, 2).reshape(S, H * D), blk["wo"], m)


def relu2(u, w_up, w_down, m):
    import jax
    import jax.numpy as jnp

    return mm(jnp.square(jax.nn.relu(mm(u, w_up, m))), w_down, m)


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
    y = relu2(u, blk["shared_up"], blk["shared_down"], m)
    for e in range(hi - lo):            # every held expert, every token
        y = y + gates[:, lo + e, None] * relu2(u, moe.w_in[e], moe.w_out[e],
                                               m)
    return y, gap


def half(blk, x, part, m):
    """Half a layer on one sequence: ``part`` is an operator (``ssm``,
    ``full``), an FFN (``moe``) or ``none``, the half a layer lacks; x
    (S, d) -> (x + its update, the per-token tie gap: zeros unless
    ``moe``)."""
    import jax.numpy as jnp

    gap = jnp.zeros(x.shape[:1])
    if part == "none":
        return x, gap
    if part == "ssm":
        return x + ssm_op(blk, x, m), gap
    if part == "full":
        return x + attention_op(blk, x, m), gap
    y, gap = expert_ffn(blk, rms_norm(x, blk["ln2"], m["eps"]), m)
    return x + y, gap


def layer(blk, x, kind, m):
    """One layer on one sequence: x (S, d) -> (S, d)."""
    op, ffn = kind
    return half(blk, half(blk, x, op, m)[0], ffn, m)[0]


# -- whole-model quantities, a sequence and a half layer at a time --------------

class Reference:
    """Jitted per-half-layer programs of one model shape, at precision
    highest.  One instance per run: compiled once per kind of half layer."""

    def __init__(self, m: dict):
        import jax

        self.m = m
        self.parts = {p for kind in m["kinds"] for p in kind} - {"none"}

        def vjp(part):
            def f(blk, x, dy):
                _, pull = jax.vjp(lambda b, a: half(b, a, part, m)[0], blk, x)
                return pull(dy)
            return jax.jit(f)

        self._half = {p: jax.jit(lambda blk, x, p=p: half(blk, x, p, m))
                      for p in self.parts}
        self._vjp = {p: vjp(p) for p in self.parts}
        self._head = jax.jit(jax.value_and_grad(
            lambda h, g, x, t: head_nll(h, g, x, t, m), argnums=(0, 1, 2)))
        self._nll = jax.jit(lambda h, g, x, t: head_nll(h, g, x, t, m))
        self._err = jax.jit(update_error)
        lo, hi = m["held"]
        self._picks = jax.jit(lambda blk, x: (router(
            blk["moe"], rms_norm(x, blk["ln2"], m["eps"]), m)[0][:, lo:hi]
            > 0).sum(0))

    def halves(self, params) -> list:
        """[(part, its layer's parameters)] in order: 2 a layer, one of them
        ``none``, so that half ``i`` reads ``hidden_states``' entry ``i``."""
        return [(part, blk) for kind, blk in layers(params, self.m)
                for part in kind]

    def half_error(self, part, blk, x, got):
        """(per-token :func:`update_error` of the program's ``got`` for the
        input ``x`` of one sequence, per-token tie gap)."""
        with highest():
            want, gap = self._half[part](blk, x)
            return _settled((self._err(x, got, want), gap))

    def held_picks(self, blk, x):
        """Of the sequence ``x`` (an expert layer's input), the picks that
        land on each held expert: (held,) counts."""
        with highest():
            return np.asarray(self._picks(blk, x))

    def sequence_losses(self, params, tokens) -> list:
        """The mean next-token cross entropy of each sequence of ``tokens``
        (B, S) on its own, by the reference's own forward pass."""
        import jax.numpy as jnp

        out = []
        with highest():
            halves = self.halves(params)
            for seq in np.asarray(tokens):
                t = jnp.asarray(seq)
                x = params["embed"][t]
                for part, blk in halves:
                    if part != "none":
                        x = _settled(self._half[part](blk, x)[0])
                out.append(float(self._nll(params["head"], params["ln_f"],
                                           x, t)) / (len(seq) - 1))
        return out

    def loss(self, params, tokens) -> float:
        """Mean next-token cross entropy of ``tokens`` (B, S)."""
        return float(np.mean(self.sequence_losses(params, tokens)))

    def loss_and_grads(self, params, tokens, at=None):
        """(mean loss, gradient tree shaped like ``params`` with numpy
        leaves) of ``tokens`` (B, S), a sequence and a half layer at a time:
        forward keeping every half layer's input, then back through them in
        reverse, every layer's part going to the host as it is made (the
        resident state, 2.7 GB of gradients and a half layer's working set
        do not fit together).  ``at`` (2L+1 arrays (B, S, d): the program's
        own ``hidden_states``) linearises every half layer at the program's
        input to it (``reference/lm.py::Reference.loss_and_grads`` says
        why)."""
        import jax
        import jax.numpy as jnp

        B, S = tokens.shape
        n = B * (S - 1)
        acc = {}

        def add(key, tree, scale=1.0):
            """``acc[key] += scale * tree``, on the host."""
            part = jax.tree.map(lambda a: np.asarray(a) * np.float32(scale),
                                _settled(tree))
            acc[key] = part if key not in acc else jax.tree.map(
                np.add, acc[key], part)

        total = 0.0
        with highest():
            halves = self.halves(params)
            g_embed = np.zeros(params["embed"].shape, np.float32)
            for b, seq in enumerate(np.asarray(tokens)):
                t = jnp.asarray(seq)
                if at is not None:
                    xs = [jnp.asarray(h[b]) for h in at]
                else:
                    xs = [params["embed"][t]]
                    for part, blk in halves:
                        xs.append(xs[-1] if part == "none" else _settled(
                            self._half[part](blk, xs[-1])[0]))
                nll, (gh, gl, dx) = self._head(params["head"],
                                               params["ln_f"], xs[-1], t)
                total += float(nll) / n
                add("head", gh, 1.0 / n)
                add("ln_f", gl, 1.0 / n)
                dx = dx / n
                del gh
                for i in reversed(range(len(halves))):
                    part, blk = halves[i]
                    if part != "none":      # a layer has one half to go back
                        gb, dx = self._vjp[part](blk, xs[i], dx)
                        add(("layer", i // 2), gb)
                        del gb
                    xs.pop()
                np.add.at(g_embed, seq, np.asarray(dx))
        blocks, k0 = [], 0
        for stacked in params["blocks"]:
            k = jax.tree.leaves(stacked)[0].shape[0]
            blocks.append(jax.tree.map(
                lambda *a: np.stack(a),
                *(acc[("layer", i)] for i in range(k0, k0 + k))))
            k0 += k
        return total, {"embed": g_embed, "head": acc["head"],
                       "blocks": tuple(blocks), "ln_f": acc["ln_f"]}
