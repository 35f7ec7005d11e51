"""Plain reference of GLM-4.7-Flash's block stack with its multi-token-
prediction module: forward pass, both losses and gradients in straightforward
``jax.numpy``, float32, matmul precision ``highest``.

No kernel, no tiles, no scan over layers, no sort, no grouped product: every
held expert is applied to every token and masked by the gate; keys and values
are re-expanded for each of the 20 heads the naive way (the one rope key a
position copied to every head) and attention is a plain softmax over the whole
key axis under an explicit boolean causal mask, made a block of ``ROWS`` query
rows at a time (an ``(S, S)`` mask of 8,192 positions and one head's scores
under it are 0.34 GB).  It works a *sequence*, a *half layer*, a query *head*
and a block of query rows at a time, so that the timed sizes fit beside the
program's resident state; that is its only concession to size.

Equations (``config.json`` of zai-org/GLM-4.7-Flash, ``model_type
glm4_moe_lite``; what the catalog's ``config`` does not carry is the family's
public DeepSeek-V3-style description and is marked † —
``configs/glm-4.7-flash-ep8.json`` lists each under ``assumed``)::

    x_0 = E[tok]
    h  = RMSNorm(x; g1)
    cq = RMSNorm(h W_qa; gq)  768;       q = cq W_qb -> 20 x [q_nope 192 ; q_rope 64]
    [ckv 512 ; kr 64] = h W_kva;         c = RMSNorm(ckv; gkv)
    [k_nope 192 ; v 256] per head = c W_kvb   (20 x 448)
    k = [k_nope ; RoPE(kr)]   one rope key a position, the same for all 20 heads
    q = [q_nope ; RoPE(q_rope)]          rotate-half pairing †, theta 1e6 over the 64 dims
    o = softmax(q k^T / sqrt(256) + causal) v       one KV head a query head
    x = x + concat(o) W_o                           5,120 -> 2,048; no bias, no QK norm, no gate
    u = RMSNorm(x; g2)
    dense layer:   y = W_down(silu(W_gate u) * W_up u)         width 10,240
    expert layer:  s = sigmoid(u W_r) † over 64;  sel = top4(s + b)  (b a buffer: noaux_tc)
                   w = s[sel] / (sum s[sel] + 1e-20) * 1.8
                   y = shared(u) + sum_{e in sel, e held} w_e W2_e(silu(W1_e u) * W3_e u)
    x = x + y
    L_main = mean over positions i < S-1 of -log softmax(RMSNorm(x_L; gf) H^T)[t_{i+1}]
    multi-token prediction † (one module):
        z_i = [RMSNorm(x_L,i; gh) ; RMSNorm(E[t_{i+1}]; ge)] W_eh      4,096 -> 2,048
        z   = one (latent, moe) layer over z at positions 0..S-1, causal
        L_mtp = mean over i < S-2 of -log softmax(RMSNorm(z; gm) H^T)[t_{i+2}]
    L = L_main + 0.3 L_mtp †

``held`` and the vocabulary slice are the program's: picks on experts that
are not held add nothing, the shared expert is whole, logits and both losses
run over the rows of ``head`` (untied; the module uses the trunk's ``E`` and
``H``).  Departures, the same as the program's
(``configs/glm-4.7-flash-ep8.json`` ``departures``): where a share of the
experts is held, the tokens take no gradient through the routing weights;
causal attention crosses document boundaries of a packed sequence; the module
keeps the sequence at ``S`` positions — position ``S-1`` reads ``E[t_0]`` for
its absent next token (a roll), which nothing earlier sees and no loss reads;
one block of query rows' scores are recomputed in the backward pass so that a
single ``(ROWS, S)`` matrix exists at a time; the gradient compared with the
program's is linearised at the program's own half-layer inputs
(``reference/lm.py`` says why) and is summed on the **host**, a half layer's
part at a time: beside 7.9 GiB of resident parameters and AdamW moments a
second 2.6 GiB tree on the device leaves a half layer's backward pass no room
(the first chip run of PR 42 ran out there: 1.26 GB wanted, 0.99 free).

The parameter tree is the program's (``models/transformer.py::init_params``
of a heterogeneous stack with an untied head and ``mtp_layers`` 1):
``embed``, ``head``, ``ln_f``, ``blocks`` (a tuple of runs of equal layers
stacked on a leading axis) and ``mtp`` (``hnorm``, ``enorm``, ``eh_proj``,
``block`` — a run of one layer —, ``norm``).
"""

from __future__ import annotations

import math

import numpy as np

from .bdlm import _settled, rope, update_error  # noqa: F401
from .lm import highest, layers, mm, rms_norm  # noqa: F401
from .swlm import embed, expert_ffn, head_nll, router, swiglu  # noqa: F401

#: query rows of one head whose scores against all S keys exist at a time
ROWS = 2048


def dims(config: dict) -> dict:
    """What the equations need, from a configuration file's keys."""
    kinds = [("latent", "dense" if i < config["first_k_dense_replace"]
              else "moe") for i in config["layers_held"]]
    if config["topk_method"] != "noaux_tc" or config["n_group"] != 1 \
            or config["topk_group"] != 1:
        raise ValueError("this family routes by noaux_tc with no group limit")
    return {"kinds": kinds, "heads": int(config["num_attention_heads"]),
            "kv_rank": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]),
            "rope": int(config["qk_rope_head_dim"]),
            "v_dim": int(config["v_head_dim"]),
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "top_k": int(config["num_experts_per_tok"]),
            "held": tuple(config["experts_held"]),
            "scale": float(config["routed_scaling_factor"])
            if config["norm_topk_prob"] else 1.0,
            "embed_scale": 1.0,
            "mtp": int(config["num_nextn_predict_layers"]),
            "mtp_weight": float(config["mtp_loss_weight"])}


# -- the equations, one sequence (S, d) at a time ------------------------------

def latent_kv(blk, h, m):
    """The keys and values of every head, re-expanded from the compressed
    vector: h (S, d) normed input -> (k (H, S, nope + rope), v (H, S, v))."""
    import jax.numpy as jnp

    S = h.shape[0]
    H, dn = m["heads"], m["nope"]
    a = mm(h, blk["wkv_a"], m)
    c = rms_norm(a[:, :m["kv_rank"]], blk["kv_a_norm"], m["eps"])
    kr = rope(a[:, None, m["kv_rank"]:], m["theta"], jnp.arange(S))[:, 0]
    kv = mm(c, blk["wkv_b"], m).reshape(S, H, dn + m["v_dim"])
    k = jnp.stack([jnp.concatenate([kv[:, i, :dn], kr], -1)
                   for i in range(H)])          # the rope key, head by head
    return k, kv[:, :, dn:].transpose(1, 0, 2)


def attention_op(blk, x, m):
    """x (S, d) -> the latent attention operator's update."""
    import jax
    import jax.numpy as jnp

    S = x.shape[0]
    H, dn, dr = m["heads"], m["nope"], m["rope"]
    pos = jnp.arange(S)
    h = rms_norm(x, blk["ln1"], m["eps"])
    cq = rms_norm(mm(h, blk["wq_a"], m), blk["q_a_norm"], m["eps"])
    q = mm(cq, blk["wq_b"], m).reshape(S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], m["theta"], pos)],
                        -1).transpose(1, 0, 2)
    k, v = latent_kv(blk, h, m)
    rows = min(ROWS, S)

    @jax.checkpoint                     # one (rows, S) score matrix at a time
    def row_block(q_rows, q_pos, kh, vh):
        s = mm(q_rows, kh.T, m) / math.sqrt(dn + dr)
        see = q_pos[:, None] >= pos[None, :]
        return mm(jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1), vh, m)

    def head(a):
        qh, kh, vh = a
        return jax.lax.map(lambda b: row_block(b[0], b[1], kh, vh),
                           (qh.reshape(S // rows, rows, dn + dr),
                            pos.reshape(S // rows, rows))
                           ).reshape(S, vh.shape[-1])

    o = jax.lax.map(head, (q, k, v)).transpose(1, 0, 2).reshape(S, -1)
    return mm(o, blk["wo"], m)


def half(blk, x, part, m):
    """Half a layer on one sequence: ``part`` is the operator (``latent``)
    or an FFN (``dense``, ``moe``); x (S, d) -> (x + its update, the
    per-token tie gap: zeros unless ``moe``)."""
    import jax.numpy as jnp

    gap = jnp.zeros(x.shape[:1])
    if part == "latent":
        return x + attention_op(blk, x, m), gap
    u = rms_norm(x, blk["ln2"], m["eps"])
    if part == "moe":
        y, gap = expert_ffn(blk, u, m)
    else:
        y = swiglu(u, blk["w_gate"], blk["w_up"], blk["w_down"], m)
    return x + y, gap


def layer(blk, x, kind, m):
    """One layer on one sequence: x (S, d) -> (S, d)."""
    op, ffn = kind
    return half(blk, half(blk, x, op, m)[0], ffn, m)[0]


def merge(mtp, table, x_last, tokens, m):
    """The module's input: ``[RMSNorm(x_L; gh) ; RMSNorm(E[t_{i+1}]; ge)]
    W_eh`` for one sequence; ``table`` is the embedding."""
    import jax.numpy as jnp

    e = table[jnp.roll(tokens, -1)]
    return mm(jnp.concatenate([rms_norm(x_last, mtp["hnorm"], m["eps"]),
                               rms_norm(e, mtp["enorm"], m["eps"])], -1),
              mtp["eh_proj"], m)


def mtp_nll(head, gain, z, tokens, m):
    """Sum over the sequence's S-2 targets of the negative log likelihood
    of the token after the next, from the module's block output."""
    import jax
    import jax.numpy as jnp

    logits = mm(rms_norm(z, gain, m["eps"]), head.T, m)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp[:-2], tokens[2:, None], -1).sum()


def module_block(params):
    """The module's one layer, unstacked."""
    import jax

    return jax.tree.map(lambda a: a[0], params["mtp"]["block"])


def merge_error(got, want):
    """Per token, the distance of the program's merged input from the
    reference's over the larger of the token's own norm and the sequence's
    root-mean-square one (no residual here: the merge *is* the state)."""
    import jax.numpy as jnp

    size = jnp.linalg.norm(want, axis=-1)
    return jnp.linalg.norm(got - want, axis=-1) / jnp.maximum(
        size, jnp.sqrt(jnp.mean(size * size) + 1e-30))


# -- whole-model quantities, a sequence and a half layer at a time --------------

class Reference:
    """Jitted per-half-layer programs of one model shape, at precision
    highest.  One instance per run: compiled once per operator / FFN kind."""

    def __init__(self, m: dict):
        import jax

        self.m = m
        self.parts = [p for kind in m["kinds"] for p in kind]
        self.module_parts = list(m["kinds"][-1]) if m["mtp"] else []

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
        self._mtp_head = jax.jit(jax.value_and_grad(
            lambda h, g, z, t: mtp_nll(h, g, z, t, m), argnums=(0, 1, 2)))
        self._mtp_nll = jax.jit(lambda h, g, z, t: mtp_nll(h, g, z, t, m))
        self._merge = jax.jit(lambda mtp, e, x, t: merge(mtp, e, x, t, m))

        def merge_vjp(mtp, table, x, t, dz):
            _, pull = jax.vjp(lambda a, e, b: merge(a, e, b, t, m),
                              mtp, table, x)
            return pull(dz)

        self._merge_vjp = jax.jit(merge_vjp)
        self._err = jax.jit(update_error)
        self._merge_err = jax.jit(merge_error)
        lo, hi = m["held"]
        self._picks = jax.jit(lambda blk, x: (router(
            blk["moe"], rms_norm(x, blk["ln2"], m["eps"]), m)[0][:, lo:hi]
            > 0).sum(0))

    def halves(self, params) -> list:
        """[(part, its layer's parameters)] of the stack in order: 2 a
        layer."""
        return [(part, blk) for kind, blk in layers(params, self.m)
                for part in kind]

    def module_halves(self, params) -> list:
        """The module's block as two more halves (empty without one)."""
        if not self.module_parts:
            return []
        blk = module_block(params)
        return [(part, blk) for part in self.module_parts]

    def half_error(self, part, blk, x, got):
        """(per-token :func:`update_error` of the program's ``got`` for the
        input ``x`` of one sequence, per-token tie gap)."""
        with highest():
            want, gap = self._half[part](blk, x)
            return _settled((self._err(x, got, want), gap))

    def held_picks(self, blk, x):
        """Of the sequence ``x`` (an expert FFN's input), the picks that land
        on each held expert: (held,) counts."""
        with highest():
            return np.asarray(self._picks(blk, x))

    def merge_error(self, params, x_last, tokens, got):
        """Per-token :func:`merge_error` of the program's merged input
        ``got`` for the trunk output ``x_last`` of one sequence."""
        import jax.numpy as jnp

        with highest():
            want = self._merge(self._merge_params(params), params["embed"],
                               x_last, jnp.asarray(tokens))
            return _settled(self._merge_err(got, want))

    @staticmethod
    def _merge_params(params) -> dict:
        return {k: params["mtp"][k] for k in ("hnorm", "enorm", "eh_proj")}

    def sequence_losses(self, params, tokens) -> list:
        """``(L_main, L_mtp)`` of each sequence of ``tokens`` (B, S) on its
        own, by the reference's own forward pass (``L_mtp`` 0.0 without a
        module)."""
        import jax.numpy as jnp

        out = []
        with highest():
            halves, module = self.halves(params), self.module_halves(params)
            for seq in np.asarray(tokens):
                t = jnp.asarray(seq)
                x = embed(params, t, self.m)
                for part, blk in halves:
                    x = _settled(self._half[part](blk, x)[0])
                main = float(self._nll(params["head"], params["ln_f"], x, t)
                             ) / (len(seq) - 1)
                mtp = 0.0
                if module:
                    z = self._merge(self._merge_params(params),
                                    params["embed"], x, t)
                    for part, blk in module:
                        z = _settled(self._half[part](blk, z)[0])
                    mtp = float(self._mtp_nll(
                        params["head"], params["mtp"]["norm"], z, t)
                    ) / (len(seq) - 2)
                out.append((main, mtp))
        return out

    def losses(self, params, tokens) -> tuple:
        """``(L, L_main, L_mtp)`` of ``tokens`` (B, S): the whole objective
        ``L_main + mtp_weight L_mtp`` and its two parts."""
        main, mtp = (float(np.mean(col))
                     for col in zip(*self.sequence_losses(params, tokens)))
        return main + self.m["mtp_weight"] * mtp, main, mtp

    def loss(self, params, tokens) -> float:
        return self.losses(params, tokens)[0]

    def loss_and_grads(self, params, tokens, at=None):
        """(the whole objective, gradient tree shaped like ``params`` with
        numpy leaves) of ``tokens`` (B, S), a sequence and a half layer at a
        time: forward keeping every half layer's input, then back — from the
        module's head through its block and its merge into the trunk's last
        state, where the main head's gradient joins, and on through the
        stack; every part goes to the host as it is made.  ``at`` (the
        program's own ``hidden_states``: 2L+1 arrays (B, S, d), then the
        module's three) linearises every half layer at the program's input
        to it (``reference/lm.py::Reference.loss_and_grads`` says why)."""
        import jax
        import jax.numpy as jnp

        B, S = tokens.shape
        n, n2 = B * (S - 1), B * (S - 2)
        w = self.m["mtp_weight"]
        acc = {}

        def add(key, tree, scale=1.0):
            """``acc[key] += scale * tree``, on the host."""
            part = jax.tree.map(lambda a: np.asarray(a) * np.float32(scale),
                                _settled(tree))
            acc[key] = part if key not in acc else jax.tree.map(
                np.add, acc[key], part)

        total = 0.0
        with highest():
            halves, module = self.halves(params), self.module_halves(params)
            n_x = len(halves) + 1
            mp = self._merge_params(params) if module else None
            g_embed = np.zeros(params["embed"].shape, np.float32)
            for b, seq in enumerate(np.asarray(tokens)):
                t = jnp.asarray(seq)
                if at is not None:
                    xs = [jnp.asarray(h[b]) for h in at[:n_x]]
                    zs = [jnp.asarray(h[b]) for h in at[n_x:]]
                else:
                    xs = [embed(params, t, self.m)]
                    for part, blk in halves:
                        xs.append(_settled(self._half[part](blk, xs[-1])[0]))
                    zs = []
                    if module:
                        zs = [self._merge(mp, params["embed"], xs[-1], t)]
                        for part, blk in module:
                            zs.append(_settled(
                                self._half[part](blk, zs[-1])[0]))
                nll, (gh, gl, dx) = self._head(params["head"],
                                               params["ln_f"], xs[-1], t)
                total += float(nll) / n
                add("head", gh, 1.0 / n)
                add("ln_f", gl, 1.0 / n)
                dx = dx / n
                del gh
                if module:
                    nll2, (gh, gm, dz) = self._mtp_head(
                        params["head"], params["mtp"]["norm"], zs[-1], t)
                    total += w * float(nll2) / n2
                    add("head", gh, w / n2)
                    add("mtp.norm", gm, w / n2)
                    dz = dz * (w / n2)
                    del gh
                    for i in reversed(range(len(module))):
                        part, blk = module[i]
                        gb, dz = self._vjp[part](blk, zs[i], dz)
                        add("mtp.block", gb)
                        del gb
                        zs.pop()
                    gmp, ge, dxl = self._merge_vjp(mp, params["embed"],
                                                   xs[-1], t, dz)
                    add("mtp.merge", gmp)
                    g_embed += np.asarray(ge)
                    dx = dx + dxl
                    del ge
                for i in reversed(range(len(halves))):
                    part, blk = halves[i]
                    gb, dx = self._vjp[part](blk, xs[i], dx)
                    add(("layer", i // 2), gb)
                    del gb
                    xs.pop()
                np.add.at(g_embed, seq,
                          np.asarray(dx) * np.float32(self.m["embed_scale"]))
        blocks, k0 = [], 0
        for stacked in params["blocks"]:
            k = jax.tree.leaves(stacked)[0].shape[0]
            blocks.append(jax.tree.map(
                lambda *a: np.stack(a),
                *(acc[("layer", i)] for i in range(k0, k0 + k))))
            k0 += k
        grads = {"embed": g_embed, "head": acc["head"],
                 "blocks": tuple(blocks), "ln_f": acc["ln_f"]}
        if module:
            grads["mtp"] = {
                **acc["mtp.merge"], "norm": acc["mtp.norm"],
                "block": jax.tree.map(lambda a: a[None], acc["mtp.block"])}
        return total, grads


def global_norm(tree) -> float:
    """The gradient tree's global norm, on the host where it lives."""
    import jax

    return float(np.sqrt(sum(np.sum(np.square(a, dtype=np.float64))
                             for a in jax.tree.leaves(tree))))
