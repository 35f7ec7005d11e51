"""Negative sampling and subsampling for word2vec.

The reference materializes a 10^8-entry unigram^0.75 table and draws
negatives by LCG index (`/root/reference/src/apps/word2vec/word2vec.h:
398-425,577-589`; regenerated **per minibatch** in the sync variant, once
globally in the async variant).  On TPU that table would be 400MB of HBM
serving random scalar reads; the alias method gives draws from the *exact*
same categorical distribution in O(1) with two vocab-sized arrays — so the
device samples (B, K) negatives per step with ``jax.random`` and no host
round-trip.  (Distribution equality, not stream equality: the reference's
table is itself only a 1e8-bucket discretization — SURVEY.md §7 hard
part (c).)

Subsampling follows the reference rule (word2vec.h:621-630): keep word w
with probability ``min(1, sqrt(sample/freq_w))`` where ``freq_w`` is the
in-corpus frequency.  Like the reference (word2vec.h:561-562), the gate
applies only to *center* positions — subsampled words still appear in
their neighbors' context windows; the batcher enforces this.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def build_unigram_alias(counts: np.ndarray, power: float = 0.75
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Walker alias tables for the unigram^power distribution.

    Returns (prob, alias): float32 (V,) acceptance thresholds and int32 (V,)
    alias targets.  Sampling: draw bucket j ~ U[0,V), accept j if
    u < prob[j] else take alias[j].
    """
    counts = np.asarray(counts, np.float64)
    if counts.ndim != 1 or len(counts) == 0:
        raise ValueError("counts must be a non-empty 1-D array")
    w = counts ** power
    p = w / w.sum() * len(w)  # mean 1
    small = np.flatnonzero(p < 1.0).tolist()
    large = np.flatnonzero(p >= 1.0).tolist()
    # the pairing is sequential; on Python floats and lists (the same
    # IEEE doubles, the same tables) it runs about twice as fast as on
    # numpy scalars: ~0.9 s of every run's set-up at 1.8 M words
    p = p.tolist()
    prob = [1.0] * len(p)
    alias = list(range(len(p)))
    while small and large:
        s, l = small.pop(), large.pop()
        prob[s] = p[s]
        alias[s] = l
        p[l] = p[l] - (1.0 - p[s])
        (small if p[l] < 1.0 else large).append(l)
    # what is left on either stack keeps ``prob`` 1: it accepts itself
    return np.asarray(prob, np.float32), np.asarray(alias, np.int32)


def _alias_draw_packed(key, prob, extra_cols, shape, take=None):
    """Shared draw core: packs ``(prob_bits, *extra_cols)`` into one
    (V, 1+len(extra_cols)) int32 table and resolves each draw with ONE
    row gather.  A scalar gather on the chip is transaction-bound: 6-7
    ns a lookup whatever the width (ledger, PR 23: the fusion that
    gathers V scalars takes 11.93 ms at V = 1.8 M, 19.9 ms at 3.0 M),
    so one packed row per draw beats one lookup per column per draw.
    The pack itself is elementwise over V, under 0.1 ms.  One copy of
    the (j, u, accept) sequence keeps every caller's draw stream
    bit-identical by construction — the parity tests reproduce training
    negatives through ``sample_alias`` while training itself uses
    ``sample_alias_slots``.

    ``take``: indices into the draw's first axis.  The draw is made at
    ``shape`` whatever a caller keeps of it (the stream is the same
    letter for letter); the table lookups, which are the cost, are made
    for the rows kept.

    Returns ``(j, accept, rows)``: bucket draws, acceptance mask, and
    the gathered packed rows (prob bits in column 0)."""
    k1, k2 = jax.random.split(key)
    V = prob.shape[0]
    j = jax.random.randint(k1, shape, 0, V)
    u = jax.random.uniform(k2, shape)
    if take is not None:
        j, u = j[take], u[take]
    packed = jnp.stack(
        [jax.lax.bitcast_convert_type(prob, jnp.int32)] + extra_cols,
        axis=1)
    rows = packed[j]                              # (*shape, 1+len(extra))
    pj = jax.lax.bitcast_convert_type(rows[..., 0], jnp.float32)
    return j, u < pj, rows


def sample_alias(key: jax.Array, prob: jax.Array, alias: jax.Array,
                 shape: Tuple[int, ...]) -> jax.Array:
    """Device-side categorical draws from alias tables.  Draws are
    bit-identical to the textbook two-gather form (same j, u, same
    compared values; prob bits round-trip exactly through the pack's
    bitcast)."""
    j, accept, rows = _alias_draw_packed(key, prob, [alias], shape)
    return jnp.where(accept, j, rows[..., 1]).astype(jnp.int32)


def alias_slot_lookups(vocab_size: int, shape: Tuple[int, ...]
                       ) -> Tuple[str, int]:
    """``(mode, lookups)``: how ``sample_alias_slots`` finds the slot of
    a rejected draw's alias word for a draw of ``shape`` over
    ``vocab_size`` words, and the scalar slot lookups that costs the
    program.  ``per_draw`` (one lookup a draw) when the call draws fewer
    negatives than the vocabulary has words, ``per_vocab`` (one a word,
    into the pack) otherwise.  Both numbers are static at trace time."""
    draws = math.prod(shape)
    if draws < vocab_size:
        return "per_draw", draws
    return "per_vocab", vocab_size


def sample_alias_slots(key: jax.Array, prob: jax.Array, alias: jax.Array,
                       slot_of_vocab: jax.Array, shape: Tuple[int, ...],
                       take=None) -> Tuple[jax.Array, jax.Array]:
    """Alias draws fused with the vocab->slot mapping: returns
    ``(negs, neg_slots)`` with ``neg_slots == slot_of_vocab[negs]``, the
    draw stream bit-identical to ``sample_alias`` + that lookup.

    The packed row ``(prob_bits, alias, slot)`` answers an accepted draw
    with one row gather.  A rejected draw also needs the slot of its
    alias word, and there are two places to look it up
    (``alias_slot_lookups`` picks the cheaper from the two shapes):

    * ``per_vocab``: a fourth column ``slot_of_vocab[alias]`` in the
      pack.  That is V scalar lookups a *program*: XLA hoists the pack
      out of a ``lax.scan``, but a step launched on its own rebuilds it
      every step (11.93 of a 14.18 ms sample phase at V = 1.8 M against
      163,840 draws; ledger, PR 23).  Right when the call draws at
      least V negatives (a 30 K-word vocabulary under a 16 K batch).
    * ``per_draw``: ``slot_of_vocab[alias[j]]`` after the row gather,
      ``prod(shape)`` lookups, and no V-sized gather in the program.

    The rule does not weigh a scan's trip count: this function cannot
    see it, and ``per_draw`` costs a trip at most what an unhoisted
    ``per_vocab`` pack would.

    ``take``: rows of the ``shape`` draw to resolve (`_alias_draw_packed`);
    the results have ``take``'s leading shape."""
    V = prob.shape[0]
    per_vocab = alias_slot_lookups(V, shape)[0] == "per_vocab"
    cols = [alias, slot_of_vocab[:V]]
    if per_vocab:
        cols.append(slot_of_vocab[alias])
    j, accept, rows = _alias_draw_packed(key, prob, cols, shape, take)
    alias_j = rows[..., 1]
    alias_slots = rows[..., 3] if per_vocab else slot_of_vocab[alias_j]
    negs = jnp.where(accept, j, alias_j).astype(jnp.int32)
    neg_slots = jnp.where(accept, rows[..., 2],
                          alias_slots).astype(jnp.int32)
    return negs, neg_slots


def subsample_keep_prob(counts: np.ndarray, sample: float) -> np.ndarray:
    """P(keep) per word (reference to_sample, word2vec.h:621-630):
    ran = 1 - sqrt(sample/freq); keep iff uniform > ran
    => P(keep) = min(1, sqrt(sample/freq)).  sample < 0 disables."""
    counts = np.asarray(counts, np.float64)
    if sample < 0:
        return np.ones(len(counts), np.float32)
    freq = counts / max(counts.sum(), 1.0)
    with np.errstate(divide="ignore"):
        keep = np.sqrt(sample / np.where(freq > 0, freq, 1.0))
    return np.minimum(keep, 1.0).astype(np.float32)
