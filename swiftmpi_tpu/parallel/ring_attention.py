"""Context parallelism for long sequences: ring attention + Ulysses.

The reference predates transformers — nothing to port (SURVEY.md §2.7
"Not present: SP/CP, ring attention, Ulysses") — but long-context is
first-class in this framework, so both standard strategies are provided as
mesh-native primitives:

* ``ring_attention`` — sequence sharded over a mesh axis; K/V blocks rotate
  around the ring via ``ppermute`` while each device folds one block per
  step into an online-softmax accumulator (flash-attention style).  ICI
  traffic per step is one K/V block; memory is O(S/n) per device.  Supports
  causal masking with block-level skipping of the always-masked products.
* ``ulysses_attention`` — all_to_all reshard: sequence-sharded activations
  become head-sharded, full-sequence attention runs locally per head group,
  then all_to_all back.  Two collectives total; requires heads % n == 0.

* ``blockwise_attention`` — one device, grouped KV heads, causal
  (``CausalMask``, the contract), over a sliding window (``WindowMask``) or
  under any other mask that names its tiles: the
  same online-softmax recurrence walked over key/value blocks, so no
  ``(S, S)`` matrix of a head ever exists in HBM, forward or backward (the
  backward pass recomputes a block's probabilities from the saved
  log-sum-exp, flash-attention style, and walks query blocks in turn as
  the forward does: a block's ``dq`` stays in the fold's carry).

All are numerically checked against ``full_attention`` in the test suite.
Layout convention: ``(batch, seq, heads, head_dim)``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from swiftmpi_tpu.parallel import attention_kernel
from swiftmpi_tpu.parallel.collectives import all_to_all, ring_permute

SEQ_AXIS = "seq"
_NEG = -1e30


def full_attention(q, k, v, causal: bool = False):
    """Single-device softmax attention golden (B, S, H, D).

    Scores and softmax are f32 regardless of input dtype — the MXU
    accumulates in f32 anyway, so asking for f32 out of the score
    einsum is free, and a bf16 softmax over S terms loses real bits.
    The probs are cast back to the value dtype so the PV einsum stays
    on the bf16 MXU path."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Sq, Sk), bool))
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32
                      ).astype(v.dtype)


# -- blockwise masked attention on one device ---------------------------------

@dataclass(frozen=True)
class CausalMask:
    """What ``blockwise_attention`` asks of a mask, in its causal case.
    The sequence is cut into ``n`` tiles of ``size`` positions; tile
    indices may be traced values.  A mask is hashable (a static argument)
    and gives

    * ``key_tiles(i, n, size) -> (lo, hi, tile)``: query tile ``i`` folds
      the key tiles ``tile(t)`` for ``t`` in ``[lo, hi)`` — every tile that
      holds a key one of its queries sees, each once.  Tiles outside the
      list are never multiplied.  The forward and the hand-written backward
      walk this one list, a query tile at a time: the forward carries the
      tile's ``(m, l, o)`` through its fold, the backward the tile's ``dq``
      (and adds a key tile's ``dk`` / ``dv`` to block-major sums);
    * ``visible(qa, kc)``: the element predicate, from absolute query
      positions ``(size, 1)`` and key positions ``(1, size)`` — or the
      other way round, ``(1, size)`` and ``(size, 1)``: it is elementwise
      and broadcasts one against the other (the forward's kernel holds its
      scores keys down, queries across);
    * ``tile(block, S) -> size``: the tile its lists are written for, at
      most ``block`` positions of the ``S`` (it must divide ``S``).

    Every query must see a key in at least one tile of its list.

    A mask made of positions alone is all of the above.  One that reads
    **data** (``parallel/sparse_attention.py::SelectedMask``: which keys a
    query keeps was decided by a learned module) is still hashable and
    static; its arrays travel beside it as ``blockwise_attention``'s
    ``mask_data``, an operand of the forward and the backward walk that
    takes no gradient, and it gives two things more:

    * ``tile_data(data, i, j, size)``: what ``visible`` needs of ``data``
      for query tile ``i`` against key tile ``j``;
    * ``visible(qa, kc, tile_data)``: the predicate with that third
      argument, broadcastable to the scores ``(B, Hkv, G, size, size)``;

    and, for the forward's kernel (``parallel/attention_kernel.py``), which
    holds one sequence's pair of tiles at a time and is handed its part of
    the data as a block of the ordinary pipeline:

    * ``block_words(data, size)``: ``data`` as one array ``(B, n rows, n
      cols)`` of 32-bit words of which tile pair ``(i, j)``'s part is block
      ``(i, j)`` of ``(rows, cols)``;
    * ``block_visible(qa, kc, block)``: the predicate ``(size, size)`` of
      one sequence from that block.

    The kernel evaluates ``visible`` / ``block_visible`` inside itself:
    they are written with comparisons, integer arithmetic and ``&`` / ``|``
    / ``~`` (Mosaic selects no booleans: no ``jnp.where`` between
    predicates)."""

    def tile(self, block, S):
        return min(block, S)

    def key_tiles(self, i, n, size):
        return 0, i + 1, lambda t: t

    def visible(self, qa, kc):
        return qa >= kc


CAUSAL = CausalMask()


@dataclass(frozen=True)
class WindowMask(CausalMask):
    """Causal attention over a sliding window: query ``i`` sees the keys
    ``j <= i`` with ``i - j < window`` (itself and the ``window - 1``
    before it).  The tile list is a band: a query tile starts at the tile
    that holds its first query's earliest key, so the tiles folded follow
    ``S * window`` and not ``S^2 / 2``.  ``window`` need not be a multiple
    of the tile."""
    window: int

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"window {self.window}: a query sees itself")

    def key_tiles(self, i, n, size):
        first = jnp.maximum(i * size - (self.window - 1), 0) // size
        return first, i + 1, lambda t: t

    def visible(self, qa, kc):
        return (qa >= kc) & (qa - kc < self.window)


def _block(x, i, size):
    """Block ``i`` of ``size`` positions along axis 1."""
    return lax.dynamic_slice_in_dim(x, i * size, size, axis=1)


def _unblock(x, shape):
    """Stacked blocks (n, B, size, ...) -> ``shape`` (B, S, ...)."""
    return jnp.moveaxis(x, 0, 1).reshape(shape)


def tile_visible(mask, data, i, j, size):
    """``mask.visible`` of query tile ``i`` against key tile ``j``:
    ``(size, size)`` from positions alone, or with ``data`` whatever shape
    the mask gives that broadcasts to the scores."""
    pos = jnp.arange(size)
    qa, kc = (i * size + pos)[:, None], (j * size + pos)[None, :]
    if data is None:
        return mask.visible(qa, kc)
    return mask.visible(qa, kc, mask.tile_data(data, i, j, size))


def _block_scores(qi, kj, i, j, size, scale, mask, data=None):
    """f32 scores (B, Hkv, G, bq, bk) of query block ``i`` against key
    block ``j``, positions the mask hides at ``_NEG``."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kj,
                   preferred_element_type=jnp.float32) * scale
    return jnp.where(tile_visible(mask, data, i, j, size), s, _NEG)


def _blockwise_fwd(q, k, v, data, size, mask):
    """q (B, S, Hkv, G, D), k / v (B, S, Hkv, D) -> (o like q, lse
    (B, S, Hkv, G) f32).  Query blocks in turn; each folds the key
    blocks its mask lists (causal: those up to its own; later ones are
    entirely in the future and are never multiplied)."""
    B, S, Hkv, G, D = q.shape
    scale = 1.0 / math.sqrt(D)

    def q_block(i):
        qi = _block(q, i, size)
        lo, hi, tile = mask.key_tiles(i, S // size, size)

        def fold(t, carry):
            m, l, o = carry
            j = tile(t)
            s = _block_scores(qi, _block(k, j, size), i, j, size, scale,
                              mask, data)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype),
                            _block(v, j, size),
                            preferred_element_type=jnp.float32)
            return m_new, l * corr + p.sum(axis=-1), o * corr[..., None] + pv

        m0 = jnp.full((B, Hkv, G, size), _NEG, jnp.float32)
        m, l, o = lax.fori_loop(
            lo, hi, fold,
            (m0, jnp.zeros_like(m0), jnp.zeros((B, Hkv, G, size, D),
                                               jnp.float32)))
        o = (o / l[..., None]).astype(q.dtype)
        return (jnp.einsum("bhgqd->bqhgd", o),
                jnp.einsum("bhgq->bqhg", m + jnp.log(l)))

    o, lse = lax.map(q_block, jnp.arange(S // size))
    return _unblock(o, q.shape), _unblock(lse, (B, S, Hkv, G))


def _forward(q, k, v, data, size, mask):
    """``_blockwise_fwd``'s ``(o, lse)`` by one of its two renderings: one
    Pallas kernel a call (``parallel/attention_kernel.py``: a query tile's
    scores, statistics and output stay in VMEM through its fold) where the
    program is lowered for a TPU and the kernel takes the dtype and the
    static shapes, the XLA walk everywhere else.  Nothing else is asked: a
    step compiled for a described chip holds the chip's walk."""
    xla = partial(_blockwise_fwd, size=size, mask=mask)
    if not (k.dtype == v.dtype == q.dtype
            and attention_kernel.takes(q.dtype, size, *q.shape[2:])):
        return xla(q, k, v, data)
    return lax.platform_dependent(
        q, k, v, data, default=xla,
        tpu=partial(attention_kernel.attn_fwd_tiles, size=size, mask=mask))


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _blockwise(q, k, v, data, size, mask):
    """-> (o, lse).  ``lse``, the log-sum-exp of every query's visible
    scores, is a statistic: no gradient flows through it (whoever reads it
    reads it under ``stop_gradient``; the backward walk drops its
    cotangent)."""
    return _forward(q, k, v, data, size, mask)


def _blockwise_vjp_fwd(q, k, v, data, size, mask):
    o, lse = _forward(q, k, v, data, size, mask)
    return (o, lse), (q, k, v, data, o, lse)


def _blockwise_vjp_bwd(size, mask, res, cts):
    """The forward's mirror: query blocks in turn, each folding the key
    blocks its mask lists.  A query block's ``dq`` is summed in the fold's
    carry (f32, in the product's own layout, as the forward's ``o``) and
    written once, cast; its ``q``, ``do``, statistics and ``delta`` are
    sliced once (a block is visited once, so ``delta`` needs no pass of
    its own).  ``dk`` / ``dv`` are f32 and block-major, ``(n, B, size,
    Hkv, D)``: a fold adds one key-sized block to each, one contiguous
    slab, where a block of a ``(B, S, ...)`` buffer that the compiler lays
    out sequence-minor is a thousand separate runs, written at a third of
    the memory's rate.  With grouped query heads the query side is the
    heavy one (``G`` times a key block), so it is the one kept still; at
    ``G`` = 1 the two sides weigh the same and this order is still the
    faster (PERF.md section 6, PR 39)."""
    q, k, v, data, o, lse = res
    do = cts[0]
    B, S, Hkv, G, D = q.shape
    scale = 1.0 / math.sqrt(D)
    n = S // size

    def add_block(x, j, update):
        return lax.dynamic_update_index_in_dim(
            x, lax.dynamic_index_in_dim(x, j, 0, keepdims=False) + update,
            j, 0)

    def q_block(i, grads):
        dq, dk, dv = grads
        qi, doi = _block(q, i, size), _block(do, i, size)
        lse_i = jnp.einsum("bqhg->bhgq", _block(lse, i, size))
        d_i = jnp.einsum("bqhg->bhgq", jnp.sum(
            doi.astype(jnp.float32)
            * _block(o, i, size).astype(jnp.float32), -1))
        lo, hi, tile = mask.key_tiles(i, n, size)

        def fold(t, carry):
            dq_i, dk, dv = carry
            j = tile(t)
            kj, vj = _block(k, j, size), _block(v, j, size)
            s = _block_scores(qi, kj, i, j, size, scale, mask, data)
            p = jnp.exp(s - lse_i[..., None])        # hidden: exp(-1e30)
            dv_j = jnp.einsum("bhgqk,bqhgd->bkhd", p.astype(do.dtype), doi,
                              preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", doi, vj,
                            preferred_element_type=jnp.float32)
            ds = (p * (dp - d_i[..., None]) * scale).astype(q.dtype)
            dk_j = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qi,
                              preferred_element_type=jnp.float32)
            dq_i = dq_i + jnp.einsum("bhgqk,bkhd->bhgqd", ds, kj,
                                     preferred_element_type=jnp.float32)
            return dq_i, add_block(dk, j, dk_j), add_block(dv, j, dv_j)

        dq_i, dk, dv = lax.fori_loop(
            lo, hi, fold,
            (jnp.zeros((B, Hkv, G, size, D), jnp.float32), dk, dv))
        dq_i = jnp.einsum("bhgqd->bqhgd", dq_i.astype(q.dtype))
        return (lax.dynamic_update_slice_in_dim(dq, dq_i, i * size, axis=1),
                dk, dv)

    zeros = jnp.zeros((n, B, size, Hkv, D), jnp.float32)
    dq, dk, dv = lax.fori_loop(0, n, q_block,
                               (jnp.zeros_like(q), zeros, zeros))
    return (dq, _unblock(dk.astype(k.dtype), k.shape),
            _unblock(dv.astype(v.dtype), v.shape), None)


_blockwise.defvjp(_blockwise_vjp_fwd, _blockwise_vjp_bwd)


def blockwise_attention(q, k, v, block: int = 512, mask=CAUSAL,
                        mask_data=None, with_lse: bool = False):
    """Masked attention on one device without an ``(S, S)`` matrix; causal
    unless ``mask`` says otherwise (:class:`CausalMask` is the contract;
    ``mask_data``: the arrays of a mask that reads data).  ``with_lse``
    returns ``(o, lse)``, ``lse`` (B, S, H) f32 the log-sum-exp of each
    query head's visible scores, a statistic that carries no gradient.

    ``q``: (B, S, H, D); ``k``, ``v``: (B, S, Hkv, D) with ``H`` a
    multiple of ``Hkv`` (each KV head serves ``H / Hkv`` query heads in
    order).  Scores, softmax statistics and accumulators are f32; the two
    products take their operands in the inputs' dtype.  ``S`` must be a
    multiple of ``block`` (a shorter sequence is one block).

    Forward and backward both walk ``mask.key_tiles``: query blocks in
    turn, each folding the key blocks listed for it.  The backward keeps a
    query block's ``dq`` (f32) in its fold's carry and adds each key
    block's ``dk`` / ``dv`` to f32 sums held a block at a time, so no f32
    buffer of the query's size exists and none is rewritten a fold."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if H % Hkv:
        raise ValueError(f"{H} query heads over {Hkv} KV heads")
    size = mask.tile(block, S)
    if S % size:
        raise ValueError(f"sequence {S} is no multiple of block {size}")
    o, lse = _blockwise(q.reshape(B, S, Hkv, H // Hkv, D), k, v, mask_data,
                        size, mask)
    o = o.reshape(B, S, H, D)
    return (o, lse.reshape(B, S, H)) if with_lse else o


def _fold_block(q, k, v, m, l, o, scale, mask):
    """One online-softmax accumulation step (flash-attention recurrence).

    q: (B, Sq, H, D); k, v: (B, Sk, H, D); m, l: (B, H, Sq) f32;
    o: (B, Sq, H, D) f32; mask: (Sq, Sk) bool or None.

    The running max/sum/output stats stay f32 across ring steps (bf16
    online-softmax statistics drift as blocks fold in); the two einsums
    keep their bf16 MXU inputs, with f32 requested out of the MXU's
    native f32 accumulation.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])            # (B, H, Sq, Sk) f32
    corr = jnp.exp(m - m_new)                    # (B, H, Sq) f32
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def ring_attention(q, k, v, mesh: Mesh, axis: str = SEQ_AXIS,
                   causal: bool = False):
    """Attention with Q, K, V sequence-sharded over ``axis``.

    Inputs/outputs are global ``(B, S, H, D)`` arrays; internally each
    device processes its S/n query block against all K/V blocks as they
    rotate around the ring.
    """
    n = int(mesh.shape[axis])
    scale = 1.0 / math.sqrt(q.shape[-1])
    spec = P(None, axis, None, None)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec, check_vma=False)
    def _ring(q_l, k_l, v_l):
        B, Sq, H, D = q_l.shape
        my = lax.axis_index(axis)

        # step 0: my own (diagonal) block — within-block causal mask
        m0 = jnp.full((B, H, Sq), _NEG, jnp.float32)
        l0 = jnp.zeros((B, H, Sq), jnp.float32)
        o0 = jnp.zeros(q_l.shape, jnp.float32)
        diag_mask = (jnp.tril(jnp.ones((Sq, Sq), bool)) if causal
                     else None)
        m1, l1, o1 = _fold_block(q_l, k_l, v_l, m0, l0, o0, scale,
                                 diag_mask)

        def body(step, carry):
            # permute first, then fold: the last rotation is never wasted
            k_cur, v_cur, m, l, o = carry
            k_cur = ring_permute(k_cur, axis)
            v_cur = ring_permute(v_cur, axis)
            src = (my - step) % n          # whose block we now hold

            def fold(c):
                m, l, o = c
                return _fold_block(q_l, k_cur, v_cur, m, l, o, scale,
                                   None)

            if causal:
                # src > my blocks are entirely in the future: skip the
                # matmuls, not just mask them (uniform predicate: every
                # device is at the same step).
                m, l, o = lax.cond(src > my, lambda c: c, fold, (m, l, o))
            else:
                m, l, o = fold((m, l, o))
            return (k_cur, v_cur, m, l, o)

        _, _, m, l, o = lax.fori_loop(
            1, n, body, (k_l, v_l, m1, l1, o1))
        l = jnp.maximum(l, 1e-20)
        return (o / l.transpose(0, 2, 1)[..., None]).astype(q_l.dtype)

    return _ring(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = SEQ_AXIS,
                      causal: bool = False):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses pattern):
    reshard seq-sharded -> head-sharded, attend over the full sequence
    locally, reshard back.  Needs H % n == 0."""
    n = int(mesh.shape[axis])
    H = q.shape[2]
    if H % n:
        raise ValueError(f"heads={H} must be divisible by axis size {n}")
    spec = P(None, axis, None, None)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec, check_vma=False)
    def _ulysses(q_l, k_l, v_l):
        # (B, S/n, H, D) -> all_to_all over heads -> (B, S, H/n, D)
        def fwd(x):
            return all_to_all(x, axis, split_axis=2, concat_axis=1)

        def bwd(x):
            return all_to_all(x, axis, split_axis=1, concat_axis=2)

        o = full_attention(fwd(q_l), fwd(k_l), fwd(v_l), causal=causal)
        return bwd(o)

    return _ulysses(q, k, v)
