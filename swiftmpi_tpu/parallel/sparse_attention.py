"""Learned sparse attention (DeepSeek sparse attention, as DeepSeek-V3.2-Exp's
report defines it): a small *indexer* scores every earlier key
for every query, the query attends the ``topk`` keys of largest score, and
the indexer learns to score as the attention it steers attends.

    I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s])            s <= t
    S_t     = the min(t + 1, topk) keys s <= t of largest I[t, s],
              the lower s on a tie
    o[t, h] = softmax_{s in S_t}(q[t, h] . k[s] / sqrt(D)) v[s]
    LI      = mean_t KL( stop_gradient(mean_h P[t, h, .]) || softmax_{S_t} I[t, .] )

(the caller folds the two scales ``1 / sqrt(heads)`` and ``1 / sqrt(dim)``
into ``w``).  One selection a query, shared by its heads.  The selection is
a constant of the step: the language-model loss reaches ``q``, ``k``, ``v``
through the attention and nothing of the indexer; ``LI`` reaches ``qi``,
``ki``, ``w`` and nothing else.

Everything here is walked a ``(query tile, key tile)`` pair at a time, so no
``(S, S)`` array of floats exists (``sparse_attention_dense`` is the plain
rendering, for short sequences and tests):

* :func:`select` — per query tile, the tile's index scores against every
  earlier key tile (one row block ``(size, S)`` f32), the exact ``topk``-th
  largest of each row by bisection over the scores' ordered bits (32 counts
  a row, no sort), ties cut at the lowest positions; the selection leaves
  as packed bits, ``S * S / 8`` bytes, with each row's log-sum-exp of its
  selected scores and the count of pairs kept;
* ``parallel/ring_attention.py::blockwise_attention`` under
  :class:`SelectedMask` with those bits as its ``mask_data`` — the masked
  tile walk, forward and backward;
* :func:`index_loss` — one more walk: a tile's attention probabilities from
  the saved log-sum-exp, their head mean, the tile's index scores again, the
  KL's terms and — in the same fold, a tile's ``softmax(I) - phat`` being
  the gradient — the indexer's gradients, kept as the residual of a
  ``custom_vjp``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from swiftmpi_tpu import obs
from swiftmpi_tpu.parallel.ring_attention import (_NEG, CausalMask, _block,
                                                  _block_scores, _unblock,
                                                  blockwise_attention,
                                                  tile_visible)


def _group(size: int) -> int:
    """Queries whose bits share a word: 32 where the tile allows."""
    return math.gcd(size, 32)


def _shifts(g: int):
    return jnp.arange(g, dtype=jnp.uint32)[None, None, :, None]


def _unpack_words(words, g: int):
    """Packed words (B, n, S) of ``g`` queries each -> bool (B, n g, S)."""
    B, n, S = words.shape
    return ((words[:, :, None, :] >> _shifts(g)) & 1).astype(bool).reshape(
        B, n * g, S)


@dataclass(frozen=True)
class SelectedMask(CausalMask):
    """Causal attention over a selection made from data: query ``t`` sees
    key ``s <= t`` iff its bit is set in ``mask_data``, ``(B, S / g, S)``
    uint32 with bit ``t % g`` of word ``[b, t // g, s]`` (``g`` = 32, or
    what of it divides the tile: :func:`select` packs them).  The selection
    is a token's, so almost no tile is empty: the tile list is the causal
    one."""

    def tile_data(self, bits, i, j, size):
        g = _group(size)
        return _unpack_words(lax.dynamic_slice(
            bits, (0, i * (size // g), j * size),
            (bits.shape[0], size // g, size)), g)

    def visible(self, qa, kc, selected):
        return ((qa >= kc) & selected)[:, None, None]

    def block_words(self, bits, size):
        return lax.bitcast_convert_type(bits, jnp.int32)

    def block_visible(self, qa, kc, words):
        g = _group(qa.shape[0])
        rows, S = words.shape
        words = jnp.broadcast_to(words[:, None, :], (rows, g, S)).reshape(
            rows * g, S)
        return (qa >= kc) & (lax.shift_right_logical(words, qa % g) & 1 == 1)


SELECTED = SelectedMask()


def unpack(bits, size: int):
    """:func:`select`'s packed selection, made with tiles of ``size``, as a
    boolean matrix (B, S, S) — for a test or a check; the walks never build
    it."""
    return _unpack_words(bits, _group(size))


def index_tile(qi, w, ki):
    """Index scores of a query tile against a key tile, f32: ``qi`` (B, bq,
    HI, dI) and ``ki`` (B, bk, dI) the product's operands (their dtype is
    the product's; it accumulates in f32), ``w`` (B, bq, HI) f32 ->
    (B, bq, bk).  ReLU, weights and the sum over heads are f32 elementwise:
    a default-precision product would round them to bf16."""
    pre = jnp.einsum("bqhd,bkd->bhqk", qi, ki,
                     preferred_element_type=jnp.float32)
    return (jax.nn.relu(pre) * jnp.swapaxes(w, 1, 2)[..., None]).sum(axis=1)


def _ordered(x):
    """f32 -> uint32 whose unsigned order is the floats' order (-inf
    lowest)."""
    u = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def top_rows(scores, causal, topk: int):
    """The selection of each row: ``scores`` (..., n, S) f32 with ``-inf``
    where ``causal`` (n, S) is false -> bool, true at the ``min(causal
    keys, topk)`` largest causal scores of a row, the lowest positions among
    equal scores at the cut.  Exact: the ``topk``-th largest value is found
    bit by bit (the largest ``tau`` with ``count(score >= tau) >= topk``),
    the cut among its equals by the same search over positions, which runs
    only where a row has more equals than it needs."""
    S = scores.shape[-1]
    # -0.0 == 0.0 for the order as for a sort
    key = _ordered(jnp.where(scores == 0, 0.0, scores))

    def count(mask):
        return mask.sum(axis=-1, dtype=jnp.int32)

    def score_bit(b, tau):
        cand = tau | (jnp.uint32(1) << (31 - b).astype(jnp.uint32))
        return jnp.where(count(key >= cand[..., None]) >= topk, cand, tau)

    tau = lax.fori_loop(0, 32, score_bit,
                        jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    above, equal = key > tau, (key == tau) & causal
    need = topk - count(above)           # of the equals, the lowest `need`
    pos = jnp.arange(S, dtype=jnp.int32)
    bits = max(1, (S - 1).bit_length())

    def cut(_):
        # the position of a row's `need`-th equal: the largest p with
        # fewer than `need` equals before it
        def pos_bit(b, p):
            cand = p | (jnp.int32(1) << (bits - 1 - b))
            return jnp.where(count(equal & (pos < cand[..., None])) < need,
                             cand, p)
        return lax.fori_loop(0, bits, pos_bit, jnp.zeros_like(need))

    last = lax.cond(jnp.any(count(equal) > need), cut,
                    lambda _: jnp.full_like(need, S - 1), None)
    return causal & (above | (equal & (pos <= last[..., None])))


def select(qi, w, ki, topk: int, size: int):
    """The step's selection, a query tile at a time: ``qi`` (B, S, HI, dI),
    ``w`` (B, S, HI), ``ki`` (B, S, dI) -> (packed bits (B, S / g, S)
    uint32 as :class:`SelectedMask` reads them, each query's log-sum-exp of
    its selected index scores (B, S) f32, the pairs kept () int32).  No
    gradient: call it on ``stop_gradient`` values."""
    B, S = w.shape[:2]
    g = _group(size)
    pos = jnp.arange(S)

    def q_tile(i):
        qt, wt = _block(qi, i, size), _block(w, i, size)

        def fill(j, rows):
            return lax.dynamic_update_slice_in_dim(
                rows, index_tile(qt, wt, _block(ki, j, size)), j * size,
                axis=2)

        rows = lax.fori_loop(0, i + 1, fill,
                             jnp.full((B, size, S), -jnp.inf, jnp.float32))
        causal = (i * size + jnp.arange(size))[:, None] >= pos[None, :]
        rows = jnp.where(causal, rows, -jnp.inf)
        with obs.named_scope("index_select"):
            keep = top_rows(rows, causal, topk)
            words = (keep.reshape(B, size // g, g, S).astype(jnp.uint32)
                     << _shifts(g)).sum(axis=2, dtype=jnp.uint32)
        lse = jax.nn.logsumexp(jnp.where(keep, rows, -jnp.inf), axis=-1)
        return words, lse, keep.sum(dtype=jnp.int32)

    words, lse, kept = lax.map(q_tile, jnp.arange(S // size))
    return (_unblock(words, (B, S // g, S)), _unblock(lse, (B, S)),
            kept.sum())


def _index_loss_walk(qi, w, ki, q, k, lse, bits, lse_i, size):
    """(LI, its gradient in qi, w, ki): query tiles in turn, each folding
    its causal key tiles.  ``q`` (B, S, Hkv, G, D), ``k`` (B, S, Hkv, D) and
    ``lse`` (B, S, Hkv, G) are the attention's, constants here."""
    B, S, Hkv, G, D = q.shape
    n, scale, tokens = S // size, 1.0 / math.sqrt(D), B * S

    def add_block(x, j, update):
        return lax.dynamic_update_index_in_dim(
            x, lax.dynamic_index_in_dim(x, j, 0, keepdims=False) + update,
            j, 0)

    def q_block(i, acc):
        kl, dqi, dw, dki = acc
        qt = _block(q, i, size)
        lse_t = jnp.einsum("bqhg->bhgq", _block(lse, i, size))
        qit, wt = _block(qi, i, size), _block(w, i, size)
        lse_it = _block(lse_i, i, size)[..., None]

        def fold(j, carry):
            kl, dqi_t, dw_t, dki = carry
            s = _block_scores(qt, _block(k, j, size), i, j, size, scale,
                              SELECTED, bits)
            phat = jnp.exp(s - lse_t[..., None]).sum(axis=(1, 2)) / (Hkv * G)
            scores, pull = jax.vjp(index_tile, qit, wt, _block(ki, j, size))
            kept = tile_visible(SELECTED, bits, i, j, size)[:, 0, 0]
            logq = jnp.where(kept, scores - lse_it, 0.0)
            kl = kl + jnp.where(phat > 0, phat * (jnp.log(phat) - logq),
                                0.0).sum()
            d_qi, d_w, d_ki = pull(
                (jnp.where(kept, jnp.exp(logq), 0.0) - phat) / tokens)
            return (kl, dqi_t + d_qi.astype(jnp.float32), dw_t + d_w,
                    add_block(dki, j, d_ki.astype(jnp.float32)))

        kl, dqi_t, dw_t, dki = lax.fori_loop(
            0, i + 1, fold,
            (kl, jnp.zeros(qit.shape, jnp.float32), jnp.zeros_like(wt), dki))
        return (kl, lax.dynamic_update_slice_in_dim(
                    dqi, dqi_t.astype(qi.dtype), i * size, axis=1),
                lax.dynamic_update_slice_in_dim(dw, dw_t, i * size, axis=1),
                dki)

    kl, dqi, dw, dki = lax.fori_loop(
        0, n, q_block,
        (jnp.float32(0.0), jnp.zeros_like(qi), jnp.zeros_like(w),
         jnp.zeros((n, B, size, ki.shape[-1]), jnp.float32)))
    return kl / tokens, (dqi, dw, _unblock(dki.astype(ki.dtype), ki.shape))


@partial(jax.custom_vjp, nondiff_argnums=(8,))
def index_loss(qi, w, ki, q, k, lse, bits, lse_i, size):
    """``LI`` of a layer: the mean over queries of the KL divergence of the
    indexer's softmax over a query's selection from the head mean of the
    attention's probabilities there (``0 log 0 = 0``).  ``q``, ``k``, ``lse``
    (the attention's own, :func:`blockwise_attention`'s ``with_lse``) make
    that target and take no gradient; ``bits`` and ``lse_i`` are
    :func:`select`'s.  The gradient in ``qi``, ``w``, ``ki`` is that of
    ``softmax(I) - phat`` on the selection, computed in the forward walk
    (the fold that has a tile's terms has its gradient) and scaled by the
    cotangent in the backward pass."""
    return _index_loss_walk(qi, w, ki, q, k, lse, bits, lse_i, size)[0]


def _index_loss_fwd(qi, w, ki, q, k, lse, bits, lse_i, size):
    loss, grads = _index_loss_walk(qi, w, ki, q, k, lse, bits, lse_i, size)
    return loss, (grads, q, k, lse, lse_i)


def _index_loss_bwd(size, res, g):
    grads, q, k, lse, lse_i = res
    return (*((g * x.astype(jnp.float32)).astype(x.dtype) for x in grads),
            jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(lse), None,
            jnp.zeros_like(lse_i))


index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def sparse_attention(q, k, v, qi, w, ki, *, topk: int, block: int = 512):
    """The operator on one device, tiled: ``q`` (B, S, H, D), ``k`` / ``v``
    (B, S, Hkv, D), the indexer's ``qi`` (B, S, HI, dI), ``w`` (B, S, HI)
    f32 and ``ki`` (B, S, dI) -> (o (B, S, H, D), the layer's index loss
    () f32, the (query, key) pairs the selection kept () int32, the
    selection itself as :func:`select` packs it).  ``S`` must be a multiple
    of ``block`` (a shorter sequence is one tile)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    size = SELECTED.tile(block, S)
    if S % size:
        raise ValueError(f"sequence {S} is no multiple of block {size}")
    stop = lax.stop_gradient
    with obs.named_scope("indexer"):
        bits, lse_i, kept = select(stop(qi), stop(w), stop(ki), topk, size)
    o, lse = blockwise_attention(q, k, v, block=block, mask=SELECTED,
                                 mask_data=bits, with_lse=True)
    with obs.named_scope("indexer"):
        loss = index_loss(qi, w, ki, stop(q.reshape(B, S, Hkv, H // Hkv, D)),
                          stop(k), stop(lse.reshape(B, S, Hkv, H // Hkv)),
                          bits, lse_i, size)
    return o, loss, kept, bits


def sparse_attention_dense(q, k, v, qi, w, ki, *, topk: int):
    """:func:`sparse_attention` with every ``(S, S)`` array whole and the
    gradients by automatic differentiation: the same selection
    (:func:`top_rows`), a masked softmax, the KL as written."""
    B, S, H, D = q.shape
    G = H // k.shape[2]
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, index_tile(qi, w, ki), -jnp.inf)
    keep = top_rows(lax.stop_gradient(scores), causal, topk)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, G, axis=2),
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    p = jax.nn.softmax(jnp.where(keep[:, None], s, _NEG), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype),
                   jnp.repeat(v, G, axis=2),
                   preferred_element_type=jnp.float32).astype(v.dtype)
    phat = lax.stop_gradient(p.mean(axis=1))
    logq = jnp.where(keep, jax.nn.log_softmax(
        jnp.where(keep, scores, -jnp.inf), axis=-1), 0.0)
    loss = jnp.where(phat > 0, phat * (jnp.log(jnp.where(phat > 0, phat, 1.0))
                                       - logq), 0.0).sum() / (B * S)
    return o, loss, keep.sum(dtype=jnp.int32)
