"""Plain reference for the skip-gram word2vec family: one skip-gram +
negative-sampling step with server-side AdaGrad, and the loss of a held-out
batch.

Straightforward ``jax.numpy`` in float32 on the host CPU backend under
``default_matmul_precision("highest")`` (``host_f32``): no kernels, no
table, no transfer layer, no sort.  The objective is eq. 4 of Mikolov et
al., NIPS 2013b; the loop is word2vec.c's skip-gram branch, of which the
reference's CBOW hot loop (``apps/word2vec/word2vec.h:550-615``) is the
specialisation.  Per valid pair ``(context c, center w)``:

    input v[c]
    for target in {h[w] (label 1), K negatives drawn for this pair (label 0)}:
        skip a negative equal to the center                      (584-586)
        f = v[c] . h[target]
        g = (label - sigmoid_clipped(f)) * alpha                 (591-598)
        h_grad[target] += g * v[c] ;  v_grad[c] += g * h[target]

then, per key and per gradient family, ``grad /= count`` (120-132: the
duplicate reduction, which is also the push contract of
``swiftmpi_tpu/transfer/api.py``; a context row counts once a valid pair, a
target row once a valid (pair, target) term) and server-side AdaGrad
(177-185): ``accum += g^2; param += lr * g / sqrt(accum + 1e-6)``.

The arithmetic both objectives share (the clipped sigmoid, the per-key
mean, AdaGrad, the comparison and its tolerance) is imported from
``reference/w2v.py``; nothing is imported from ``swiftmpi_tpu``.

Departure from the reference, shared with the system under test: the
sigmoid is exact inside [-6, 6] where the reference reads a 1000-bucket
table.

**Tolerance**: ``RTOL`` = 1e-4 under ``compare``'s rule, as for CBOW, and
it holds for pairs for the same reason: the system under test computes the
same f32 terms in another order (it sorts the ``pairs * (K + 1)`` target
slots and segment-sums duplicates, where this file scatter-adds), so what
separates the two is rounding, ~1e-7 a sum term.  Skip-gram makes the
duplicates the rule, not the exception: a center's ``h`` row is a target of
every one of its up to 2W pairs, so a dropped duplicate shows as >= 1/(2W)
= 10 % of that row's gradient at window 5, a thousand times the tolerance;
bf16 rows (2^-9 of a row's value) or a bf16 matmul pass (2^-8) show as
they do for CBOW.
"""

from __future__ import annotations

import numpy as np

from .w2v import (RTOL, _adagrad, _mean_by_key, _sigmoid_clipped,  # noqa: F401
                  compare, host_f32)


def _terms(h, v, t_ids, t_valid, c_ids, c_valid, alpha):
    """Inputs, targets, logits, labels, ``g`` and validity of every
    (pair, target) term."""
    import jax.numpy as jnp

    v_in = v[c_ids]                                          # (P, d)
    h_t = h[t_ids]                                           # (P, K+1, d)
    f = jnp.einsum("pd,pkd->pk", v_in, h_t)
    label = jnp.zeros(f.shape, jnp.float32).at[:, 0].set(1.0)
    valid = t_valid & c_valid[:, None]
    g = jnp.where(valid, (label - _sigmoid_clipped(f)) * alpha, 0.0)
    return v_in, h_t, f, label, g, valid


def _step(rows, t_ids, t_valid, c_ids, c_valid, alpha, lr):
    import jax.numpy as jnp

    h, v = rows["h"], rows["v"]
    d = h.shape[1]
    v_in, h_t, _f, _label, g, valid = _terms(h, v, t_ids, t_valid, c_ids,
                                             c_valid, alpha)
    h_contrib = g[..., None] * v_in[:, None, :]              # (P, K+1, d)
    v_contrib = jnp.einsum("pk,pkd->pd", g, h_t)             # (P, d)
    h_grad = _mean_by_key(t_ids.reshape(-1), valid.reshape(-1),
                          h_contrib.reshape(-1, d), h.shape[0])
    v_grad = _mean_by_key(c_ids, c_valid, v_contrib, v.shape[0])
    h2, h2sum = _adagrad(h, rows["h2sum"], h_grad, lr)
    v2, v2sum = _adagrad(v, rows["v2sum"], v_grad, lr)
    return {"h": h2, "h2sum": h2sum, "v": v2, "v2sum": v2sum}


def step(rows: dict, t_ids, t_valid, c_ids, c_valid, alpha: float,
         lr: float) -> dict:
    """One train step on the rows it touches.

    ``rows``: ``h``/``h2sum`` of the ``n_t`` distinct target rows and
    ``v``/``v2sum`` of the ``n_c`` distinct context rows, before the step.
    ``t_ids`` (pairs, K+1) index the target rows (column 0 the center,
    label 1), ``c_ids`` (pairs,) the context (input) rows.  ``c_valid``
    masks the pairs the window leaves dead, ``t_valid`` the negatives equal
    to their center.  Returns the same four arrays after the step.  One
    jitted program on the CPU backend: compiled once, then read from the
    persistent cache."""
    import jax

    with host_f32():
        out = jax.jit(_step)(
            {f: np.asarray(a) for f, a in rows.items()},
            np.asarray(t_ids, np.int32), np.asarray(t_valid),
            np.asarray(c_ids, np.int32), np.asarray(c_valid),
            np.float32(alpha), np.float32(lr))
        return {f: np.asarray(a) for f, a in out.items()}


def _held_out(h, v, t_ids, t_valid, c_ids, c_valid, alpha):
    import jax
    import jax.numpy as jnp

    _v, _h, f, label, g, valid = _terms(h, v, t_ids, t_valid, c_ids,
                                        c_valid, alpha)
    error = jnp.sum(1e4 * g * g) / jnp.maximum(valid.sum(), 1)
    sign = 2.0 * label - 1.0
    ns = jnp.where(valid, -jax.nn.log_sigmoid(sign * f), 0.0).sum() \
        / jnp.maximum(c_valid.sum(), 1)
    return error, ns


def held_out_loss(h, v, t_ids, t_valid, c_ids, c_valid, alpha: float):
    """(error, ns) of a batch of pairs against the given rows.

    ``error`` is the reference's own training error, ``Error::norm`` of
    word2vec.h:593 and 442-457 and what ``train()`` returns per iteration:
    the mean over the valid (pair, target) terms of ``1e4 * g^2`` with
    ``g = (label - sigmoid_clipped(f)) * alpha``.  It is bounded (a term
    adds at most ``1e4 * alpha^2``).  ``ns`` is the negative-sampling
    objective a pair, ``-log sigmoid(f_center) - sum_k log sigmoid(-f_neg_k)``
    (Mikolov et al. 2013b, eq. 4): unbounded, and on a table a few steps old
    it follows the norms of the few most frequent words' rows."""
    import jax

    with host_f32():
        error, ns = jax.jit(_held_out)(
            np.asarray(h), np.asarray(v), np.asarray(t_ids, np.int32),
            np.asarray(t_valid), np.asarray(c_ids, np.int32),
            np.asarray(c_valid), np.float32(alpha))
        return float(error), float(ns)
