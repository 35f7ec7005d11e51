"""Plain reference of Keye-VL-2.0's language stack with learned sparse
attention: forward pass, both losses and gradients in straightforward
``jax.numpy``, float32, matmul precision ``highest``.

No kernel, no tiles, no packed bits, no bisection, no grouped product: the
index scores and a head's attention scores are whole rows of an ``(S, S)``
matrix, ``ROWS`` query rows at a time (its only concession to size, with one
sequence, one layer at a time); the selection is made by ``jnp.argsort``;
every held expert is applied to every token and masked by the gate; the
gradient is ``jax.vjp`` of the equations with their two ``stop_gradient``s.

Equations (``config.json`` of Kwai-Keye/Keye-VL-2.0-30B-A3B, the language
stack; DeepSeek sparse attention as DeepSeek-V3.2-Exp's report defines it;
every layer alike; ``d`` 2,048, ``H`` 32 on ``Hkv`` 4 of ``D`` 128, the
indexer ``HI`` 16 of ``dI`` 64, ``k`` 2,048)::

    u = RMSNorm(x)
    q = RoPE(RMSNorm_D(u W_q)),  k = RoPE(RMSNorm_D(u W_k)),  v = u W_v
    ub = stop_gradient(u)
    qI = RoPE(ub W_qI)  (HI x dI),  kI = RoPE(LayerNorm(ub W_kI))  (dI),
    w = ub W_w / sqrt(HI)
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(dI)        s <= t
    S_t = the min(t + 1, k) keys s <= t of largest I[t, s], the lower s on a tie
    P[t, h, .] = softmax_{s in S_t}(q[t, h] . k[s, g(h)] / sqrt(D))
    h = x + concat_h(sum_{s in S_t} P[t, h, s] v[s, g(h)]) W_o
    phat[t, .] = stop_gradient(mean_h P[t, h, .])
    LI = mean_t sum_{s in S_t} phat[t, s] (log phat[t, s] - log softmax_{S_t}(I[t, .])[s])
    m = RMSNorm(h);  r = softmax(m W_r) over 128;  sel = top8(r);  g = r[sel] / sum r[sel]
    y = h + sum_{e in sel, e held} g_e W2_e(silu(W1_e m) * W3_e m)
    L = mean_{t < S-1} -log softmax(RMSNorm(y_L) W_head^T)[token_{t+1}]  +  sum_layers LI

``0 log 0 = 0``.  ``held`` and the vocabulary slice are the program's: picks
on experts that are not held add nothing, logits and loss run over the rows
of ``head``.  Departures, the same as the program's
(``configs/keye-vl-2.0-30b-a3b-ep8.json`` ``departures``): on a share the
tokens take no gradient through the routing weights; a block of rows is
recomputed in the backward pass; the gradient compared with the program's is
linearised at the program's own half-layer inputs *and selections*
(``Reference.loss_and_grads(at=, keeps=)``; ``reference/lm.py`` says why for
the routing, and a selection is a discrete choice of the same kind: the
selection itself is held to this file's by ``index_block``).

``m["operands"]``, ``m["index_sum"]``, ``m["statistics"]``,
``m["loss_dtype"]`` and ``m["approx_topk"]`` (all absent in the reference
proper) make the reference
*as a lower precision or an approximate selection would compute it*
(``tools/salm_lower_precision.py``).

The parameter tree is the program's (``models/transformer.py::init_params``
of a heterogeneous stack with an untied head).
"""

from __future__ import annotations

import math

import numpy as np

from .bdlm import _settled, expert_ffn, update_error  # noqa: F401
from .lm import global_norm, highest, layers, mm, rms_norm, rope  # noqa: F401

#: query rows whose index scores and attention scores against all S keys
#: exist at a time: (32 heads, ROWS, S) f32 is 512 MiB at 16,384
ROWS = 256


def dims(config: dict) -> dict:
    """What the equations need, from a configuration file's keys."""
    sa = config["sa_config"]
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("the indexer has one key a position")
    return {"kinds": [("sparse", "moe")] * int(config["num_hidden_layers"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "top_k": int(config["num_experts_per_tok"]),
            "held": tuple(config["experts_held"]),
            "index_heads": int(sa["indexer_num_heads"]),
            "index_dim": int(sa["indexer_head_dim"]),
            "index_topk": int(sa["topk"])}


# -- the equations, one sequence (S, d) at a time -------------------------------

def layer_norm(x, g, b, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def indexer(blk, u, m):
    """The normed input ``u`` (S, d), detached -> qI (S, HI, dI), w (S, HI),
    kI (S, dI)."""
    import jax

    S = u.shape[0]
    HI, dI = m["index_heads"], m["index_dim"]
    ub = jax.lax.stop_gradient(u)
    qi = rope(mm(ub, blk["wq_idx"], m).reshape(S, HI, dI), m["theta"])
    ki = rope(layer_norm(mm(ub, blk["wk_idx"], m), blk["idx_ln_g"],
                         blk["idx_ln_b"], m["eps"])[:, None], m["theta"])[:, 0]
    return qi, mm(ub, blk["w_idx"], m) / math.sqrt(HI), ki


def index_rows(qi_r, w_r, ki, m):
    """Index scores of the query rows ``qi_r`` (R, HI, dI), ``w_r`` (R, HI)
    against every key ``ki`` (S, dI) -> (R, S), not yet masked."""
    import jax
    import jax.numpy as jnp

    pre = jax.lax.map(lambda q_j: mm(q_j, ki.T, m),
                      qi_r.transpose(1, 0, 2))               # (HI, R, S)
    terms = jax.nn.relu(pre) * w_r.T[:, :, None]
    dtype = m.get("index_sum")
    if dtype is not None:        # the sum as a lower precision would make it
        terms = terms.astype(dtype)
    return terms.sum(0).astype(jnp.float32) / math.sqrt(m["index_dim"])


def select_rows(scores, causal, m):
    """(R, S) bool: the ``min(causal keys, k)`` largest causal scores of
    each row, the lower position on a tie — a stable descending sort."""
    import jax
    import jax.numpy as jnp

    R, S = scores.shape
    k = min(m["index_topk"], S)
    masked = jnp.where(causal, scores, -jnp.inf)
    if m.get("approx_topk"):     # its value: the recall asked of it
        _, best = jax.lax.approx_max_k(masked, k,
                                       recall_target=m["approx_topk"])
    else:
        best = jnp.argsort(-masked, axis=-1, stable=True)[:, :k]
    keep = jnp.zeros((R, S), bool).at[jnp.arange(R)[:, None], best].set(True)
    return keep & causal


def _row_block(q_r, qi_r, w_r, row0, keep_r, kh, vh, ki, m):
    """``R`` query rows from position ``row0`` on -> (their heads' outputs
    (R, H, D), the sum of their KL terms, the pairs they keep, their index
    scores' selection).  ``kh`` / ``vh`` (H, S, D): every query head's own
    copy of its KV head.  ``keep_r`` (R, S) bool or None: a selection given,
    else made here."""
    import jax
    import jax.numpy as jnp

    R, S = q_r.shape[0], ki.shape[0]
    D = q_r.shape[-1]
    causal = jnp.arange(S)[None, :] <= (row0 + jnp.arange(R))[:, None]
    scores = index_rows(qi_r, w_r, ki, m)
    keep = select_rows(jax.lax.stop_gradient(scores), causal, m) \
        if keep_r is None else keep_r & causal
    stats = m.get("statistics")

    def head(a):
        q_h, k_h, v_h = a
        s = jnp.where(keep, mm(q_h, k_h.T, m) / math.sqrt(D), -jnp.inf)
        if stats is not None:    # softmax as a lower precision would make it
            s = s.astype(stats)
        p = jax.nn.softmax(s, axis=-1).astype(jnp.float32)
        return mm(p, v_h, m), p

    o, p = jax.lax.map(head, (q_r.transpose(1, 0, 2), kh, vh))
    phat = jax.lax.stop_gradient(p.mean(0))
    logq = jnp.where(keep, jax.nn.log_softmax(
        jnp.where(keep, scores, -jnp.inf), axis=-1), 0.0)
    kl = jnp.where(phat > 0, phat * (jnp.log(jnp.where(phat > 0, phat, 1.0))
                                     - logq), 0.0).sum()
    return o.transpose(1, 0, 2), kl, keep.sum()


def sparse_op(blk, x, m, keep=None):
    """x (S, d) -> (the operator's update (S, d), the layer's index loss,
    the pairs its selection keeps).  ``keep`` (S, S) bool: attend (and
    teach the indexer on) this selection instead of the one made here."""
    import jax
    import jax.numpy as jnp

    S = x.shape[0]
    H, Hkv = m["heads"], m["kv_heads"]
    D = blk["wq"].shape[1] // H
    u = rms_norm(x, blk["ln1"], m["eps"])
    q = rope(rms_norm(mm(u, blk["wq"], m).reshape(S, H, D), blk["q_norm"],
                      m["eps"]), m["theta"])
    k = rope(rms_norm(mm(u, blk["wk"], m).reshape(S, Hkv, D), blk["k_norm"],
                      m["eps"]), m["theta"])
    v = mm(u, blk["wv"], m).reshape(S, Hkv, D)
    qi, w, ki = indexer(blk, u, m)
    rows = min(ROWS, S)
    if S % rows:
        raise ValueError(f"{S} positions are no multiple of {rows} rows")
    nb = S // rows
    group = H // Hkv                    # query head i reads KV head i // group
    kh = jnp.repeat(k.transpose(1, 0, 2), group, axis=0)
    vh = jnp.repeat(v.transpose(1, 0, 2), group, axis=0)

    @jax.checkpoint                     # one block's (H, rows, S) at a time
    def block(q_r, qi_r, w_r, row0, keep_r, kh, vh, ki):
        return _row_block(q_r, qi_r, w_r, row0, keep_r, kh, vh, ki, m)

    o, kl, kept = jax.lax.map(
        lambda a: block(*a, kh, vh, ki),
        (q.reshape(nb, rows, H, D), qi.reshape(nb, rows, *qi.shape[1:]),
         w.reshape(nb, rows, -1), jnp.arange(nb) * rows,
         None if keep is None else keep.reshape(nb, rows, S)))
    return (mm(o.reshape(S, H * D), blk["wo"], m), kl.sum() / S, kept.sum())


def index_block(blk, x, row0, rows, m):
    """(index scores (rows, S) with -inf at the non-causal pairs, the
    selection (rows, S) bool) of ``rows`` query rows from ``row0`` on, for
    the layer input x (S, d): what the program's own scores and selection
    of those rows are held to."""
    import jax
    import jax.numpy as jnp

    S = x.shape[0]
    qi, w, ki = indexer(blk, rms_norm(x, blk["ln1"], m["eps"]), m)
    causal = jnp.arange(S)[None, :] <= (row0 + jnp.arange(rows))[:, None]
    scores = index_rows(jax.lax.dynamic_slice_in_dim(qi, row0, rows),
                        jax.lax.dynamic_slice_in_dim(w, row0, rows), ki, m)
    return (jnp.where(causal, scores, -jnp.inf),
            select_rows(scores, causal, m))


def half(blk, x, part, m, keep=None):
    """Half a layer on one sequence: ``part`` is ``sparse`` or ``moe``; x
    (S, d) -> (x + its update, the per-token tie gap: zeros unless ``moe``,
    the layer's index loss: 0 unless ``sparse``)."""
    import jax.numpy as jnp

    if part == "moe":
        y, gap = expert_ffn(blk, x, m)
        return x + y, gap, jnp.float32(0.0)
    y, li, _kept = sparse_op(blk, x, m, keep)
    return x + y, jnp.zeros(x.shape[:1]), li


def head_nll(head, ln_f, x, tokens, m):
    """Sum over the sequence's S-1 targets of the next-token negative log
    likelihood, from the last layer's output, through the untied head."""
    import jax
    import jax.numpy as jnp

    logits = mm(rms_norm(x, ln_f, m["eps"]), head.T, m)
    dtype = m.get("loss_dtype")
    if dtype is not None:        # the loss as a lower precision would make it
        logits = logits.astype(dtype)
    logp = jax.nn.log_softmax(logits, axis=-1).astype(jnp.float32)
    return -jnp.take_along_axis(logp[:-1], tokens[1:, None], -1).sum()


# -- whole-model quantities, a sequence and a half layer at a time --------------

class Reference:
    """Jitted per-half-layer programs of one model shape, at precision
    highest.  One instance per run: compiled once per part (twice for
    ``sparse``: with a selection given and without)."""

    def __init__(self, m: dict):
        import jax

        self.m = m
        self.parts = [p for kind in m["kinds"] for p in kind]

        def vjp(part):
            def f(blk, x, keep, dy, dli):
                (_, li), pull = jax.vjp(
                    lambda b, a: half(b, a, part, m, keep)[::2], blk, x)
                return pull((dy, dli)), li
            return jax.jit(f)

        self._half = {p: jax.jit(lambda blk, x, keep, p=p:
                                 half(blk, x, p, m, keep))
                      for p in set(self.parts)}
        self._vjp = {p: vjp(p) for p in set(self.parts)}
        self._head = jax.jit(jax.value_and_grad(
            lambda h, g, x, t: head_nll(h, g, x, t, m), argnums=(0, 1, 2)))
        self._nll = jax.jit(lambda h, g, x, t: head_nll(h, g, x, t, m))
        self._err = jax.jit(update_error)
        self._index = jax.jit(lambda blk, x, row0, rows:
                              index_block(blk, x, row0, rows, m),
                              static_argnums=3)

        def selection(blk, x):
            rows = min(ROWS, x.shape[0])
            return jax.lax.map(
                lambda row0: index_block(blk, x, row0, rows, m)[1],
                jax.numpy.arange(0, x.shape[0], rows)).reshape(
                    x.shape[0], x.shape[0])

        self._selection = jax.jit(selection)

    def halves(self, params) -> list:
        """[(part, its layer's parameters)] in order: 2 a layer."""
        return [(part, blk) for kind, blk in layers(params, self.m)
                for part in kind]

    def _keep(self, keeps, i, b):
        """Half layer ``i``'s selection of sequence ``b`` from ``keeps``
        (a list over the layers of (B, S, S) bool) or None."""
        import jax.numpy as jnp

        if keeps is None or self.parts[i] != "sparse":
            return None
        return _settled(jnp.asarray(keeps[i // 2][b]))

    def half_error(self, part, blk, x, got, keep=None):
        """(per-token :func:`update_error` of the program's ``got`` for the
        input ``x`` (S, d) of one sequence, per-token tie gap, the layer's
        index loss)."""
        with highest():
            want, gap, li = self._half[part](blk, x, keep)
            return _settled((self._err(x, got, want), gap, li))

    def index_block(self, blk, x, row0: int, rows: int):
        """:func:`index_block`, jitted."""
        with highest():
            return _settled(self._index(blk, x, row0, rows))

    def selection(self, blk, x):
        """The reference's own selection for the layer input x (S, d), whole:
        (S, S) bool."""
        with highest():
            return _settled(self._selection(blk, x))

    def sequence_losses(self, params, tokens, keeps=None) -> list:
        """[(main, index)] of each sequence of ``tokens`` (B, S) on its
        own: the mean next-token cross entropy of its S-1 targets and the
        layers' index losses summed, by the reference's own forward pass
        (under the selections ``keeps`` where given)."""
        import jax.numpy as jnp

        S = tokens.shape[1]
        out = []
        with highest():
            halves = self.halves(params)
            for b, seq in enumerate(np.asarray(tokens)):
                t = jnp.asarray(seq)
                x, index = params["embed"][t], 0.0
                for i, (part, blk) in enumerate(halves):
                    x, _gap, li = _settled(self._half[part](
                        blk, x, self._keep(keeps, i, b)))
                    index += float(li)
                out.append((float(self._nll(params["head"], params["ln_f"],
                                            x, t)) / (S - 1), index))
        return out

    def losses(self, params, tokens, keeps=None):
        """(the objective, its main part, its index part) of ``tokens``
        (B, S): means over the sequences."""
        main, index = np.mean(self.sequence_losses(params, tokens, keeps), 0)
        return float(main + index), float(main), float(index)

    def loss_and_grads(self, params, tokens, at=None, keeps=None):
        """((objective, main, index), gradient tree shaped like ``params``),
        a sequence and a half layer at a time: forward keeping every half
        layer's input, then back through them in reverse, the index loss's
        cotangent ``1 / B`` entering at every sparse half.  ``at`` (2L+1
        arrays (B, S, d): the program's own ``hidden_states``) linearises
        every half layer at the program's input to it, ``keeps`` at the
        program's selection."""
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
            main, index = [], []
            for b, seq in enumerate(np.asarray(tokens)):
                t = jnp.asarray(seq)
                if at is not None:
                    # host copies: one half layer's input is put on the
                    # device at a time, and waited for (nine at once, their
                    # transfers racing the first programs' allocations, made
                    # the run's memory peak follow the host's timing: 12.08 or
                    # 12.28 GB, my chip runs, PR 49)
                    xs = [h[b] for h in at]
                else:
                    xs = [params["embed"][t]]
                    for i, (part, blk) in enumerate(halves):
                        xs.append(_settled(self._half[part](
                            blk, xs[-1], self._keep(keeps, i, b))[0]))
                nll, (gh, gl, dx) = self._head(
                    params["head"], params["ln_f"],
                    _settled(jnp.asarray(xs[-1])), t)
                main.append(float(nll))
                g_head, g_lnf = g_head + gh / n, g_lnf + gl / n
                dx = dx / n
                for i in reversed(range(len(halves))):
                    part, blk = halves[i]
                    (gb, dx), li = _settled(self._vjp[part](
                        blk, _settled(jnp.asarray(xs[i])),
                        self._keep(keeps, i, b), dx, jnp.float32(1.0 / B)))
                    index.append(float(li))
                    g_layers[i // 2] = _settled(jax.tree.map(
                        jnp.add, g_layers[i // 2], gb))
                    del gb
                    xs.pop()
                g_embed = _settled(g_embed.at[t].add(dx))
                del dx, xs
        blocks, k0 = [], 0
        for stacked in params["blocks"]:
            k = jax.tree.leaves(stacked)[0].shape[0]
            blocks.append(_settled(jax.tree.map(
                lambda *a: jnp.stack(a), *g_layers[k0:k0 + k])))
            k0 += k
        del g_layers
        main, index = sum(main) / n, sum(index) / B
        return (main + index, main, index), {
            "embed": g_embed, "head": g_head, "blocks": tuple(blocks),
            "ln_f": g_lnf}
