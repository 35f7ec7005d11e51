"""The selective state-space recurrence of a Mamba-2 layer as a chunked scan.

One sequence's recurrence, a head ``h`` with a ``(P, N)`` state (arXiv
2405.21060; ``x`` the head's ``P`` inputs, ``b`` / ``c`` its group's ``N``-wide
input and output maps, ``dt >= 0`` its step size, ``a <= 0`` its decay rate)::

    s_t = exp(dt_t a) s_{t-1} + dt_t x_t (x) b_t        y_t = s_t c_t

run position by position it is ``S`` dependent steps over ``H P N`` values
each, and its backward pass would keep every position's state (8,192 x 64 x 64
x 128 f32 = 17 GB a layer).  :func:`chunked_scan` computes the same ``y`` in
chunks of ``chunk`` positions (the state-space duality's block decomposition):

* inside a chunk the quadratic form — ``y_t += sum_{s <= t} exp(cs_t - cs_s)
  (c_t . b_s) dt_s x_s`` with ``cs`` the running sum of ``dt a`` inside the
  chunk: two matrix products a chunk, ``(Q, N) x (N, Q)`` a group and ``(Q, Q)
  x (Q, P)`` a head;
* between chunks the state alone: a chunk's own contribution ``sum_s exp(cs_Q
  - cs_s) dt_s x_s (x) b_s``, carried ``h_c = exp(cs_Q) h_{c-1} + ...`` by a
  ``lax.scan`` over the ``S / Q`` chunks, and read ``y_t += exp(cs_t) h_{c-1}
  c_t``.

Its backward pass is the transpose of those products and of that scan (plain
autodiff): what it keeps is a chunk's ``(Q, Q)`` decay matrix a head (32 KB a
position at ``Q`` = 128, ``H`` = 64) and the ``S / Q`` chunk states (128 MiB a
sequence at 64 x 64 x 128), never a position's state.

Precision: ``dt a``, its running sums and every decay are float32, and ``exp``
is only ever taken of a non-positive difference (a later position's sum minus
an earlier one's; the upper triangle is masked *before* the ``exp``), so no
decay overflows however long the chunk; the chunk states are carried in
float32.  The products' operands are ``compute_dtype`` (bf16 in a
deployment), accumulated in float32.

No segment ids: a packed sequence's recurrence runs across its document
boundaries, as ``blockwise_attention``'s causal mask does; no state enters
from or leaves to another call (nothing here generates).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def n_chunks(positions: int, chunk: int) -> int:
    """Chunks :func:`chunked_scan` walks for one sequence of ``positions``
    (the last one padded where ``positions`` is no multiple)."""
    return -(-positions // min(chunk, positions))


def chunked_scan(x, dt, a, b, c, *, chunk: int = 128, compute_dtype=None):
    """``y (B, S, H, P)`` float32 of the recurrence above from a zero state:
    ``x (B, S, H, P)``, ``dt (B, S, H) >= 0``, ``a (H,) <= 0``, ``b`` and
    ``c`` ``(B, S, G, N)`` with head ``h`` reading group ``h // (H / G)``.

    A sequence that is no multiple of ``chunk`` is padded behind its last
    position with steps of size 0 (decay 1, no input): the recurrence is
    causal, so no real position sees them, and their outputs are cut."""
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    if H % G:
        raise ValueError(f"{H} heads are no multiple of {G} groups")
    Q = min(chunk, S)
    nc, Hg = n_chunks(S, chunk), H // G
    pad = nc * Q - S
    if pad:
        x, dt, b, c = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b, c))
    dtype = compute_dtype or x.dtype

    def dot(spec, lhs, rhs):
        return jnp.einsum(spec, lhs.astype(dtype), rhs.astype(dtype),
                          preferred_element_type=jnp.float32)

    xc = x.reshape(B, nc, Q, G, Hg, P)
    bc, cc = (t.reshape(B, nc, Q, G, N) for t in (b, c))
    # (B, nc, G, Hg, Q): a head's positions last, so that the (Q, Q) forms
    # below have the two position axes minor
    dtc = dt.astype(jnp.float32).reshape(B, nc, Q, G, Hg).transpose(
        0, 1, 3, 4, 2)
    cs = jnp.cumsum(dtc * a.astype(jnp.float32).reshape(G, Hg, 1), axis=-1)

    # inside a chunk: decay[t, s] = exp(cs_t - cs_s) for s <= t, else 0
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(seen, cs[..., :, None] - cs[..., None, :],
                              -jnp.inf))
    scores = dot("bctgn,bcsgn->bcgts", cc, bc)              # c_t . b_s
    weights = scores[:, :, :, None] * decay * dtc[..., None, :]
    y = dot("bcghts,bcsghp->bctghp", weights, xc)

    # a chunk's own state, what is left of it at the chunk's end
    to_end = jnp.exp(cs[..., -1:] - cs) * dtc               # (B, nc, G, Hg, Q)
    own = dot("bcsghp,bcsgn->bcghpn",
              xc * to_end.transpose(0, 1, 4, 2, 3)[..., None], bc)

    # between chunks: h_c = exp(cs_Q) h_{c-1} + own_c; chunk c reads h_{c-1}
    def carry(h, step):
        keep, new = step
        return keep[..., None, None] * h + new, h

    _, entering = lax.scan(
        carry, jnp.zeros((B, G, Hg, P, N), jnp.float32),
        (jnp.exp(cs[..., -1]).swapaxes(0, 1), own.swapaxes(0, 1)))
    y = y + dot("bctgn,bcghpn->bctghp", cc, entering.swapaxes(0, 1)) \
        * jnp.exp(cs).transpose(0, 1, 4, 2, 3)[..., None]
    return y.reshape(B, nc * Q, H, P)[:, :S]
