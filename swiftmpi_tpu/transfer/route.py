"""Owner routing of a row-sharded table's pulls and pushes.

Everything here runs INSIDE a manual mesh axis (``jax.shard_map``) of size
``n`` over which the table's rows are split: ``state_l`` is this chip's
shard, ``slots`` are this chip's share of the batch (global slot ids, ``-1``
padding; row ``s`` lives on chip ``s // rows_per_shard``), and the only
traffic between chips is ``all_to_all`` of what is actually routed.

* `pull`: bucket the requests by owner, exchange them, the owner gathers
  from its shard, exchange the rows back, place them in request order.
* `push`: sum the chip's own duplicates first (the sparse push's sort and
  segment-sum), which also lays the distinct rows out by owner; exchange
  them in passes of whole row ranges; the owner runs the one-chip sparse
  push on what it received, its shard a table of its own.

No request is dropped, by construction.  An exchange offers each owner a
fixed `bucket_slots` of the chip's batch and is repeated until every bucket
is drained (the count is known at run time, one ``pmax`` / ``psum``); a
single exchange suffices unless an owner is asked for more than 1.25 x
its even share.  A push's passes cut the owner's rows at bounds every sender
agrees on (a ``pmin``), so all contributions to a row — at most one a
sender, after its own sum — meet in ONE pass: ``mean`` divides by the
global count and the access rule sees the global sum, whatever the skew.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from swiftmpi_tpu import obs


def bucket_slots(batch: int, n: int) -> int:
    """Slots a chip offers each owner in one exchange: 1.25 x the even
    share of its ``batch``, in whole sublane tiles, at most the batch.
    Hashed keys spread evenly (the hottest key's owner is asked for ~1.2 x
    the mean under Zipf 1.0, and a batch is never all valid), so the slack
    is what one exchange needs; what it does not hold takes another."""
    even = -(-batch // n)
    return max(1, min(batch, -(-(even + even // 4) // 8) * 8))


def _exchange(x, axis):
    """``x[o]`` goes to chip ``o``; row ``s`` of the result came from chip
    ``s``."""
    return jax.lax.all_to_all(x, axis, 0, 0, tiled=True)


def pull(state_l, slots, fields, axis: str, n: int, gather):
    """Rows of ``fields`` at ``slots``, zero rows at padding, fetched from
    their owners.  ``gather(arr, rows, valid)`` is the owner's masked
    gather.  Returns ``(rows by field, requests routed, slots offered)``,
    the last two this chip's own counts."""
    B = slots.shape[0]
    cap = next(iter(state_l.values())).shape[0]
    C = bucket_slots(B, n)
    valid = slots >= 0
    owner = jnp.where(valid, slots // cap, n)
    local = jnp.where(valid, slots - owner * cap, 0)
    # a request's place in its owner's bucket: one running count an owner
    mine_of = owner[None, :] == jnp.arange(n, dtype=jnp.int32)[:, None]
    upto = jnp.cumsum(mine_of.astype(jnp.int32), axis=1)           # (n, B)
    place = jnp.sum(jnp.where(mine_of, upto - 1, 0), axis=0)       # (B,)
    rounds = jax.lax.pmax(jnp.max(-(-upto[:, -1] // C)), axis)

    def one_round(r, out):
        col = place - r * C
        now = valid & (col >= 0) & (col < C)
        req = jnp.full((n, C), -1, jnp.int32).at[
            jnp.where(now, owner, n), jnp.where(now, col, 0)].set(
                local, mode="drop", unique_indices=True)
        asked = _exchange(req, axis).reshape(-1)
        at = jnp.where(now, owner * C + col, 0)
        for f in fields:
            rows = gather(state_l[f], asked, asked >= 0)
            back = _exchange(rows.reshape(n, C, -1), axis).reshape(n * C, -1)
            out[f] = jnp.where(now[:, None], jnp.take(back, at, axis=0),
                               out[f])
        return out

    out = jax.lax.fori_loop(0, rounds, one_round, {
        f: jnp.zeros((B, state_l[f].shape[1]), state_l[f].dtype)
        for f in fields})
    return (out, jnp.sum(valid, dtype=jnp.int32) * len(fields),
            rounds.astype(jnp.int32) * (n * C * len(fields)))


def push(state_l, slots, grads, counts, axis: str, n: int, combine,
         owner_push):
    """``grads`` at ``slots`` summed onto their owners' shards.

    ``combine(slots, grads, counts, capacity)`` is the sparse push's own
    duplicate reduction: ``(rows, sums, weights)`` with the distinct slots
    ascending at the head of ``rows``, ``capacity`` behind them, and
    ``weights`` a row's summed multiplicity (``None``: no mean is taken).
    ``owner_push(state_l, rows, grads, counts)`` is the one-chip sparse
    push on the shard (rows local to it, ``-1`` padding); it returns the
    new shard and what it wrote, ``int32[3]`` (distinct rows, tiles moved
    for them, copies that moved those).  Returns ``(shard, written, rows
    routed, slots offered)``, the counts this chip's own."""
    B = slots.shape[0]
    cap = next(iter(state_l.values())).shape[0]
    capacity = n * cap
    C = bucket_slots(B, n)
    rows, sums, weights = combine(slots, grads, counts, capacity)
    with obs.named_scope("dedup"):
        # ascending slots are grouped by owner: bucket o is a slice
        first = jnp.searchsorted(
            rows, jnp.arange(n + 1, dtype=jnp.int32) * cap).astype(jnp.int32)
        ends = first[1:]
        base = jnp.arange(n, dtype=jnp.int32) * cap
        lane = jnp.arange(C, dtype=jnp.int32)

    def left(cursor):
        return jax.lax.psum(jnp.sum(ends - cursor), axis) > 0

    def one_pass(carry):
        state_l, cursor, _, written, routed, offered = carry
        with obs.named_scope("dedup"):
            # the first row a sender cannot fit bounds the pass for every
            # sender, so a row's contributions never straddle two passes
            limit = jnp.where(ends - cursor > C,
                              rows[jnp.clip(cursor + C, 0, B - 1)], capacity)
            bound = jax.lax.pmin(limit, axis)
            upto = jnp.clip(jnp.searchsorted(rows, bound).astype(jnp.int32),
                            cursor, ends)
            # a bucket near the batch's end starts early: the slots it
            # shares with what is not its own are masked
            at = jnp.maximum(jnp.minimum(cursor, B - C), 0)
            j = at[:, None] + lane[None, :]
            own = (j >= cursor[:, None]) & (j < upto[:, None])

            def cut(x):
                return jnp.stack([jax.lax.dynamic_slice_in_dim(x, at[o], C)
                                  for o in range(n)])
            got_rows = _exchange(
                jnp.where(own, cut(rows) - base[:, None], -1),
                axis).reshape(-1)
            got = {f: _exchange(cut(g), axis).reshape(n * C, -1)
                   for f, g in sums.items()}
            got_counts = None if weights is None else _exchange(
                jnp.where(own, cut(weights), 0.0), axis).reshape(-1)
        state_l, wrote = owner_push(state_l, got_rows, got, got_counts)
        return (state_l, upto, left(upto), written + wrote,
                routed + jnp.sum(upto - cursor) * len(sums),
                offered + n * C * len(sums))

    zero = jnp.int32(0)
    state_l, _, _, written, routed, offered = jax.lax.while_loop(
        lambda carry: carry[2], one_pass,
        (dict(state_l), first[:-1], left(first[:-1]),
         jnp.zeros((3,), jnp.int32), zero, zero))
    return state_l, written, routed, offered
