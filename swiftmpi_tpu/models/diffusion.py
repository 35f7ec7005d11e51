"""Block-diffusion training (BD3-LM's objective): the noise, the attention
mask and the loss's assembly.  models/transformer.py runs the trunk.

A sequence ``x0`` of ``S`` tokens is cut into blocks of ``Lb`` positions,
``b(i) = i // Lb``, ``Nb = S / Lb`` of them.  A step draws one ``u ~ U[0, 1)``
a sequence and gives block ``b`` the mask rate

    t_b = eps + (1 - eps) * ((u + b / Nb) mod 1)          in [eps, 1]

(a low-discrepancy draw: a sequence's blocks cover the rates evenly), then
``m_i ~ Bernoulli(t_b(i))`` a position and ``xt_i = MASK if m_i else x0_i``.
The trunk sees ``z = [xt ; x0]`` — 2S positions, the noised copy first —
with position ids ``[0..S-1 ; 0..S-1]``, and query ``a`` sees key ``c`` iff
(:func:`visible`)

    a noisy, c noisy:  b(a) == b(c)              a noisy, c clean:  b(c - S) <  b(a)
    a clean, c clean:  b(c - S) <= b(a - S)      a clean, c noisy:  never

so a noised block reads itself and the clean text before it, and the clean
copy is block-causal.  The loss is the cross entropy of the masked
positions' own tokens (no shift), weighted ``1 / t``:

    loss = (1 / (B S)) * sum_i  m_i / t_b(i) * -log softmax(logits_i)[x0_i]

``cfg`` is a ``TransformerConfig`` (``diffusion_block``, ``mask_token``,
``noise_eps``).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


def block_noise(key, tokens, cfg):
    """The step's noise for ``tokens`` (B, S) int32: ``(noisy (B, S) int32
    with ``cfg.mask_token`` at the masked positions, weights (B, S) f32 =
    m / t)``.  Deterministic in ``key``: whoever holds the key (the
    trainer's :meth:`~swiftmpi_tpu.models.trainer.Trainer.noise_key`) draws
    the step's noise again."""
    B, S = tokens.shape
    Lb, eps = cfg.diffusion_block, cfg.noise_eps
    if S % Lb:
        raise ValueError(f"sequence {S} is no multiple of diffusion_block "
                         f"{Lb}")
    n_blocks = S // Lb
    k_u, k_m = jax.random.split(key)
    u = jax.random.uniform(k_u, (B, 1), jnp.float32)
    offset = jnp.arange(n_blocks, dtype=jnp.float32) / n_blocks
    t = eps + (1.0 - eps) * jnp.mod(u + offset, 1.0)        # (B, Nb)
    t = jnp.repeat(t, Lb, axis=1)                           # (B, S)
    masked = jax.random.uniform(k_m, (B, S), jnp.float32) < t
    noisy = jnp.where(masked, jnp.int32(cfg.mask_token), tokens)
    return noisy, jnp.where(masked, 1.0 / t, 0.0)


def trunk_input(noisy, tokens):
    """``[x_t ; x_0]`` (B, 2S): what the trunk runs under this objective."""
    return jnp.concatenate([noisy, tokens], axis=1)


def attention_inputs(S: int, cfg) -> dict:
    """What ``models/transformer.py``'s trunk is told beside
    :func:`trunk_input` of ``S``-token sequences: both halves count
    ``positions`` from 0, and who sees whom is the ``mask``."""
    return {"positions": jnp.tile(jnp.arange(S, dtype=jnp.float32), 2),
            "mask": BlockDiffusionMask(S, cfg.diffusion_block)}


def weighted_loss(nll, weights):
    """The loss from the noised half's per-position ``nll`` (B, S)."""
    return (weights * nll).sum() / nll.size


def visible(qa, kc, S: int, Lb: int):
    """Whether query ``qa`` sees key ``kc`` (absolute indices into the 2S
    positions of ``[x_t ; x_0]``, broadcast against each other)."""
    q_noisy, k_noisy = qa < S, kc < S
    bq = jnp.where(q_noisy, qa, qa - S) // Lb
    bk = jnp.where(k_noisy, kc, kc - S) // Lb
    # written with and / or alone: Mosaic selects no booleans, and the
    # attention kernel evaluates this inside itself
    return ((k_noisy & q_noisy & (bq == bk))
            | (~k_noisy & ((bk < bq) | (~q_noisy & (bk == bq)))))


@dataclass(frozen=True)
class BlockDiffusionMask:
    """The mask as ``parallel/ring_attention.py::blockwise_attention`` takes
    one (its ``CausalMask`` says what each method gives), over ``2 * seq``
    positions in tiles of ``size``, ``N = seq / size`` a half: noisy query
    tile ``i`` folds clean key tiles ``0..i`` and noisy tile ``i``; clean
    query tile ``i`` folds clean tiles ``0..i`` (``N^2 + 2N`` of the
    ``4 N^2`` tile pairs)."""
    seq: int        # S: positions a half
    block: int      # Lb

    def tile(self, block, S):
        return min(block, self.seq)         # a tile lies inside one half

    def _halves(self, n, size):
        if self.seq % size or n * size != 2 * self.seq:
            raise ValueError(f"{n} tiles of {size} are not two halves of "
                             f"{self.seq} positions")
        return self.seq // size

    def key_tiles(self, i, n, size):
        N = self._halves(n, size)
        noisy = i < N
        return (0, jnp.where(noisy, i + 2, i - N + 1),
                lambda t: jnp.where(noisy & (t == i + 1), i, N + t))

    def visible(self, qa, kc):
        return visible(qa, kc, self.seq, self.block)
