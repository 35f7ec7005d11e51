"""Plain reference for the word2vec family: one CBOW + negative-sampling
step with server-side AdaGrad, and the loss of a held-out batch.

Straightforward ``jax.numpy`` in float32 on the host CPU backend under
``default_matmul_precision("highest")``: no kernels, no table, no transfer
layer, no sort.  It follows the reference's hot loop as SURVEY.md cites it
(``apps/word2vec/word2vec.h``), per center word:

    neu1 = sum of the context words' input vectors v            (550-575)
    for target in {center (label 1), K negatives (label 0)}:
        skip a negative equal to the center                      (584-586)
        f = neu1 . h[target]
        g = (label - sigmoid_clipped(f)) * alpha                 (591-598)
        h_grad[target] += g * neu1 ;  neu1e += g * h[target]
    v_grad[context_j] += neu1e for each context word             (606-612)

then, per key and per gradient family, ``grad /= count`` (120-132: the
duplicate reduction, which is also the push contract of
``swiftmpi_tpu/transfer/api.py``: sum a key's contributions, divide by how
many there were, apply the access rule once) and server-side AdaGrad
(177-185): ``accum += g^2; param += lr * g / sqrt(accum + 1e-6)``.

Departure from the reference, shared with the system under test: the
sigmoid is exact inside [-6, 6] where the reference reads a 1000-bucket
table (its discretisation error is ~1e-3).

**Tolerance** (``RTOL``): the system under test computes the same f32
arithmetic in another order (XLA lowers both einsums to multiply + reduce
on the vector unit, sorts the batch and segment-sums duplicates), so what
separates the two is rounding: ~1e-7 a sum term, worst on rows whose
contributions nearly cancel.  A row passes when
``|got - want| <= RTOL * |want| + RTOL * rms(want - before)`` over the
field, with RTOL = 1e-4: some fifteen times the 4e-6 to 7e-6 observed on the
chip (``PERF.md`` section 6), and a tenth to a twentieth of what bf16 rows
(2^-9 = 2e-3 of a row's value), a bf16 matmul pass (2^-8) or a dropped
duplicate (>= 1/count of the gradient) would show.
"""

from __future__ import annotations

from contextlib import contextmanager

import numpy as np

MAX_EXP = 6.0
FUDGE = 1e-6
RTOL = 1e-4


@contextmanager
def host_f32():
    """The reference's arithmetic context: CPU backend, true f32 matmuls."""
    import jax

    with jax.default_device(jax.devices("cpu")[0]), \
            jax.default_matmul_precision("highest"):
        yield


def _sigmoid_clipped(f):
    import jax.numpy as jnp

    s = 1.0 / (1.0 + jnp.exp(-jnp.clip(f, -MAX_EXP, MAX_EXP)))
    return jnp.where(f > MAX_EXP, 1.0, jnp.where(f < -MAX_EXP, 0.0, s))


def _mean_by_key(ids, valid, contrib, n_keys):
    """Per-key mean of the valid contributions: (n_keys, d)."""
    import jax.numpy as jnp

    w = valid.astype(jnp.float32)
    safe = jnp.where(valid, ids, 0)
    sums = jnp.zeros((n_keys, contrib.shape[-1]), jnp.float32).at[safe].add(
        contrib * w[:, None])
    counts = jnp.zeros((n_keys,), jnp.float32).at[safe].add(w)
    return sums / jnp.maximum(counts, 1.0)[:, None]


def _adagrad(param, accum, grad, lr):
    import jax.numpy as jnp

    accum = accum + grad * grad
    return param + lr * grad / jnp.sqrt(accum + FUDGE), accum


def _step(rows, t_ids, t_valid, c_ids, c_valid, alpha, lr):
    import jax.numpy as jnp

    h, v = rows["h"], rows["v"]
    B, T = t_ids.shape
    d = h.shape[1]
    v_ctx = v[c_ids] * c_valid[..., None]                    # (B, 2W, d)
    neu1 = v_ctx.sum(axis=1)                                 # (B, d)
    h_t = h[t_ids]                                           # (B, K+1, d)
    f = jnp.einsum("bd,bkd->bk", neu1, h_t)
    label = jnp.zeros((B, T), jnp.float32).at[:, 0].set(1.0)
    g = jnp.where(t_valid, (label - _sigmoid_clipped(f)) * alpha, 0.0)
    h_contrib = g[..., None] * neu1[:, None, :]              # (B, K+1, d)
    neu1e = jnp.einsum("bk,bkd->bd", g, h_t)                 # (B, d)
    v_contrib = jnp.broadcast_to(neu1e[:, None, :], v_ctx.shape)
    h_grad = _mean_by_key(t_ids.reshape(-1), t_valid.reshape(-1),
                          h_contrib.reshape(-1, d), h.shape[0])
    v_grad = _mean_by_key(c_ids.reshape(-1), c_valid.reshape(-1),
                          v_contrib.reshape(-1, d), v.shape[0])
    h2, h2sum = _adagrad(h, rows["h2sum"], h_grad, lr)
    v2, v2sum = _adagrad(v, rows["v2sum"], v_grad, lr)
    return {"h": h2, "h2sum": h2sum, "v": v2, "v2sum": v2sum}


def step(rows: dict, t_ids, t_valid, c_ids, c_valid, alpha: float,
         lr: float) -> dict:
    """One train step on the rows it touches.

    ``rows``: ``h``/``h2sum`` of the ``n_t`` distinct target rows and
    ``v``/``v2sum`` of the ``n_c`` distinct context rows, before the step.
    ``t_ids`` (B, K+1) index the target rows (column 0 the center, label
    1), ``c_ids`` (B, 2W) the context rows; ``*_valid`` mask padding,
    negatives equal to their center, and centers with no context.
    Returns the same four arrays after the step.  One jitted program on
    the CPU backend: compiled once, then read from the persistent cache."""
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

    neu1 = (v[c_ids] * c_valid[..., None]).sum(axis=1)
    f = jnp.einsum("bd,bkd->bk", neu1, h[t_ids])
    label = jnp.zeros(f.shape, jnp.float32).at[:, 0].set(1.0)
    g = jnp.where(t_valid, (label - _sigmoid_clipped(f)) * alpha, 0.0)
    error = jnp.sum(1e4 * g * g) / jnp.maximum(t_valid.sum(), 1)
    sign = 2.0 * label - 1.0
    ns = jnp.where(t_valid, -jax.nn.log_sigmoid(sign * f), 0.0).sum() \
        / jnp.maximum(t_valid[:, 0].sum(), 1)
    return error, ns


def held_out_loss(h, v, t_ids, t_valid, c_ids, c_valid, alpha: float):
    """(error, ns) of a batch against the given rows.

    ``error`` is the reference's own training error, ``Error::norm`` of
    word2vec.h:593 and 442-457 and what ``train()`` returns per iteration:
    the mean over the valid (center, target) pairs of ``1e4 * g^2`` with
    ``g = (label - sigmoid_clipped(f)) * alpha``.  It is bounded (a pair
    adds at most ``1e4 * alpha^2``), so a few rows that have grown large do
    not decide it.  ``ns`` is the negative-sampling objective a center,
    ``-log sigmoid(f_center) - sum_k log sigmoid(-f_neg_k)`` (Mikolov et al.
    2013b, eq. 4, with the CBOW context sum as input vector): unbounded, and
    on a table a few steps old it follows the norms of the few most frequent
    words' rows, which differ from seed to seed."""
    import jax

    with host_f32():
        error, ns = jax.jit(_held_out)(
            np.asarray(h), np.asarray(v), np.asarray(t_ids, np.int32),
            np.asarray(t_valid), np.asarray(c_ids, np.int32),
            np.asarray(c_valid), np.float32(alpha))
        return float(error), float(ns)


def _compare(got, want, before):
    import jax.numpy as jnp

    scale = jnp.sqrt(jnp.mean(jnp.square(want - before)))
    err = jnp.abs(got - want) / (jnp.abs(want) + scale + 1e-30)
    return jnp.max(err), scale, jnp.isfinite(got).all()


def compare(got: dict, want: dict, before: dict, rtol: float = RTOL) -> dict:
    """Per field: the largest ``|got - want| / (|want| + rms(want -
    before))`` over the rows, and whether it is within ``rtol``."""
    import jax

    out = {}
    with host_f32():
        for f in want:
            worst, scale, finite = (float(x) for x in jax.jit(_compare)(
                np.asarray(got[f]), np.asarray(want[f]),
                np.asarray(before[f])))
            out[f] = {"max_err": worst, "rms_update": scale,
                      "ok": bool(finite and worst <= rtol and scale > 0)}
    return out
