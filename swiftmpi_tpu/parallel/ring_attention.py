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

Both are numerically checked against ``full_attention`` in the test suite
on an 8-device mesh.  Layout convention: ``(batch, seq, heads, head_dim)``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

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
