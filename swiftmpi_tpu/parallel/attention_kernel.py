"""``blockwise_attention``'s forward walk as one Pallas TPU kernel a call.

The XLA walk (``parallel/ring_attention.py::_blockwise_fwd``) folds one
key tile at a time for the whole ``(B, Hkv, G)`` batch, so a fold's scores,
probabilities, statistics and output accumulator are each an HBM buffer
and a fusion of their own.  Here a grid step is one ``(query tile, key
tile)`` pair of the mask's lists for one sequence and a few KV heads
(:func:`heads_per_step`): the query heads that share the key tile take it
in turn, each multiplying the tile whole, and a query tile's running ``m``
/ ``l`` and f32 output stay in VMEM scratch from the first pair of its list
to the last.  Only ``q``, ``k``, ``v`` (and a data mask's words) are read
from HBM and ``o``, ``lse`` written, once.

The lists are data the grid reads: :func:`tile_pairs` evaluates
``mask.key_tiles`` when the call is traced and flattens the lists into one
axis of the grid — the pair's query tile, its key tile, whether it opens or
closes its list, scalar-prefetched
— so the grid has no step outside a list, and a tile that is in no list is
never copied, let alone multiplied.

Inside, scores stand keys down and queries across (``k q^T``): a query's
statistics are one lane of a row vector — ``m`` and ``l`` of a whole tile
are four registers, and the reductions over keys are elementwise maxima
and sums of registers, no lane against another — and the output
accumulates as ``(D, queries)``.  That is why ``q``, ``v`` and ``o`` cross
the call sequence-minor (``(B, Hkv G D, S)``, ``(B, Hkv D, S)``): both
products are then plain ``A @ B`` on the blocks as they arrive, and the
transposes around the call are bitcasts where the compiler keeps those
arrays sequence-minor anyway, as it does for the hand-written backward
walk (v5e, every LM cell of the benchmark: ``tests/test_compile_v5e_lm.py``).
``k`` stays ``(B, S, Hkv D)``; ``lse`` leaves as ``(B, Hkv, G, S)``.

The two products of a step's query heads are written so that the MXU has
the next head's scores to multiply while the vector unit is in this
head's softmax.  A head takes the tile's queries whole, not 128 at a time:
a quarter of the body to trace, which a run's set-up pays in every program
that holds the kernel.

The numbers that chose the order (24.9 -> 22.1 ms a layer at ``sdar``'s
shape), the whole-tile unit (128 queries at a time was 4 % faster at ``D``
= 128) and `HEADS` are PR 50's scratch runs on a v5e (``PERF.md`` section
6): read on an earlier body of this kernel by a script that is not in the
tree, so ``scripts/attention_bwd_micro.py`` does not reproduce them.  Whoever
tunes this reads them again first.
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from swiftmpi_tpu.utils.xla_env import pallas

_NEG = -1e30
LANES = 128
#: a pair opens / closes its query tile's list
FIRST, LAST = 1, 2
#: query heads a grid step holds at least, so that one's products have
#: another's softmax to run under (PR 50's scratch runs, as above: `G` = 1,
#: `D` = 256, a layer 5.47 ms at one head a step, 4.59 at four, 4.54 at
#: eight)
HEADS = 4
#: what the kernel may ask of VMEM
_VMEM_LIMIT = 100 << 20


def heads_per_step(Hkv: int, G: int, D: int) -> int:
    """KV heads a grid step takes: as many as fill a block's 128 lanes
    where a head is narrower, and as many as give the step `HEADS` query
    heads to run one's products under another's softmax (the fewest that
    divide ``Hkv``)."""
    for hb in range(1, Hkv + 1):
        if Hkv % hb == 0 and hb * D % LANES == 0 and hb * G >= min(
                HEADS, Hkv * G):
            return hb
    return Hkv


def takes(dtype, size: int, Hkv: int, G: int, D: int) -> bool:
    """Whether the kernel takes these operands: bf16 or f32, tiles whose
    scores fill whole registers, heads of whole or half a 128-lane block
    (``D`` 64, 128, 256 are in the benchmark's cells), and what it holds of
    a query tile inside the VMEM it asks for."""
    hb = heads_per_step(Hkv, G, D)
    return (jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                 jnp.dtype(jnp.float32))
            and size % LANES == 0
            and (D % LANES == 0 or (LANES % D == 0 and D >= 64))
            and hb * D % LANES == 0
            and vmem_bytes(dtype, size, hb * G, D) <= _VMEM_LIMIT)


def vmem_bytes(dtype, size: int, C: int, D: int) -> int:
    """The VMEM a call needs for the ``C`` query heads of a grid step:
    double-buffered blocks of ``q``, ``o``, ``k``, ``v`` and ``lse``, the
    f32 accumulator and statistics, and the scratch two heads' scores and
    weights and the pair's mask go through."""
    item = jnp.dtype(dtype).itemsize
    blocks = 2 * (2 * C + 2) * size * D * item + 2 * C * size * 4
    scratch = size * C * D * 4 + 2 * C * size * 4
    return blocks + scratch + size * size * (3 * 4 + 2 * item)


def _at_trace_time():
    """Evaluate JAX operations now, on the host where it has a CPU backend:
    the lists are a few hundred integers, and an accelerator compiles each
    tiny operation on them anew in every process."""
    stack = contextlib.ExitStack()
    stack.enter_context(jax.ensure_compile_time_eval())
    with contextlib.suppress(RuntimeError):
        stack.enter_context(jax.default_device(jax.devices("cpu")[0]))
    return stack


@functools.lru_cache(maxsize=64)
def tile_pairs(mask, n: int, size: int):
    """``mask.key_tiles`` of all ``n`` query tiles as one list of pairs:
    (query tile, key tile, ``FIRST`` | ``LAST``) int32 arrays, a query
    tile's pairs together and in its list's order.  Evaluated when the call
    is traced (the lists are the mask's and the shapes', never the
    batch's)."""
    with _at_trace_time():
        def row(i):
            lo, hi, tile = mask.key_tiles(i, n, size)
            t = lo + jnp.arange(n, dtype=jnp.int32)
            return (jnp.asarray(tile(t), jnp.int32) + jnp.zeros_like(t),
                    jnp.asarray(hi - lo, jnp.int32))
        tiles, counts = jax.vmap(row)(jnp.arange(n, dtype=jnp.int32))
        tiles, counts = np.asarray(tiles), np.asarray(counts)
    if counts.min() < 1 or counts.max() > n:
        raise ValueError(f"{mask}: a query tile lists {counts.min()}.."
                         f"{counts.max()} of {n} key tiles")
    q_of = np.repeat(np.arange(n, dtype=np.int32), counts)
    t_of = np.concatenate([np.arange(c) for c in counts])
    k_of = tiles[q_of, t_of].astype(np.int32)
    if k_of.min() < 0 or k_of.max() >= n:
        raise ValueError(f"{mask}: key tiles {k_of.min()}..{k_of.max()} "
                         f"of {n}")
    flags = (FIRST * (t_of == 0) + LAST * (t_of == counts[q_of] - 1))
    return q_of, k_of, flags.astype(np.int32)


def _visible_t(mask, i, j, size, block):
    """``mask.visible`` of query tile ``i`` against key tile ``j`` inside
    the kernel, keys down and queries across as the kernel holds its
    scores: ``(size, size)`` bool ``[key, query]`` from the tile's
    positions and, for a mask that reads data, the pair's block of its
    words."""
    if block is not None:
        qa = i * size + lax.broadcasted_iota(jnp.int32, (size, 1), 0)
        kc = j * size + lax.broadcasted_iota(jnp.int32, (1, size), 1)
        return mask.block_visible(qa, kc, block).astype(jnp.int32).T == 1
    qa = i * size + lax.broadcasted_iota(jnp.int32, (1, size), 1)
    kc = j * size + lax.broadcasted_iota(jnp.int32, (size, 1), 0)
    return jnp.broadcast_to(mask.visible(qa, kc), (size, size))


def attn_fwd_tiles(q, k, v, data, size: int, mask):
    """The forward walk: ``q`` (B, S, Hkv, G, D), ``k`` / ``v`` (B, S, Hkv,
    D) -> (``o`` like ``q``, ``lse`` (B, S, Hkv, G) f32), as
    ``_blockwise_fwd`` gives them.  ``data``: the arrays of a mask that
    reads data (``mask.block_words`` says how a pair's block of them is
    cut), else ``None``.  Jitted, so that a program that calls it at one
    shape many times (a layer's forward pass and its recomputation, every
    scanned group of layers) traces the kernel's body once: the body is
    written out head by head, and tracing it costs a run's set-up a
    second.  JAX keys a traced function by its context too, and a layer's
    recomputation is traced under an abstract mesh that is empty where the
    forward pass's is none at all: where no mesh is set the call names the
    empty one itself, so that both find the one trace."""
    mesh = jax.sharding.get_abstract_mesh()
    with jax.sharding.use_abstract_mesh(mesh) if mesh.empty \
            else contextlib.nullcontext():
        return _attn_fwd_tiles(q, k, v, data, size, mask)


@functools.partial(jax.jit, static_argnames=("size", "mask"))
def _attn_fwd_tiles(q, k, v, data, size: int, mask):
    pl, pltpu = pallas()

    B, S, Hkv, G, D = q.shape
    n = S // size
    # exp(scale (s - m)) = exp2((s - m) scale log2 e): one multiply
    c2 = (1.0 / math.sqrt(D)) * math.log2(math.e)
    scale = 1.0 / math.sqrt(D)
    hb = heads_per_step(Hkv, G, D)
    C = hb * G                              # query heads a grid step takes
    q_of, k_of, flags = tile_pairs(mask, n, size)
    words = None if data is None else mask.block_words(data, size)

    def kernel(q_of, k_of, flags, q_ref, k_ref, v_ref, *refs):
        w_ref = refs[0] if words is not None else None
        o_ref, lse_ref, m_s, l_s, acc_s, *bufs, seen_s = refs[
            words is not None:]
        s_s, e_s = bufs[:2], bufs[2:]
        p = pl.program_id(2)
        flag = flags[p]

        @pl.when(flag & FIRST != 0)
        def _open():
            m_s[...] = jnp.full_like(m_s, _NEG)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

        # every pair goes under its mask, wholly visible or not, and pays
        # for it (a load, a compare and a select a head a score): a second,
        # unmasked body for the pairs a mask could vouch for was worth 1.3 %
        # of `trinity-ep16-16k-t16k`'s step and 3.5 % of `sdar-ep8-8k-t16k`'s
        # and cost a second trace and lowering of the body, which took
        # `trinity`'s set-up over its bound (PERF.md sections 6 and 7, PR 50)
        seen_s[...] = _visible_t(mask, q_of[p], k_of[p], size,
                                 None if w_ref is None else w_ref[...]
                                 ).astype(jnp.int32)

        def scores(c):
            """Query head ``c``'s scores, hidden ones at ``_NEG``, into
            its slot of the scratch -> their maxima over keys, ``(8,
            size)``: registers against registers, no sublane of one
            against another."""
            h = c // G
            s = jnp.dot(k_ref[:, h * D:(h + 1) * D],
                        q_ref[c * D:(c + 1) * D, :],
                        preferred_element_type=jnp.float32)
            s = jnp.where(seen_s[...] != 0, s, _NEG)
            s_s[c % 2][...] = s
            return s.reshape(size // 8, 8, size).max(axis=0)

        # the MXU takes its products in the order written: the next
        # head's scores go before this head's softmax, so that they
        # (and the head before's second product) run under it
        top = scores(0)
        for c in range(C):
            h, m8 = c // G, top
            if c + 1 < C:
                top = scores(c + 1)
            m_prev = m_s[c:c + 1, :]                      # (1, size)
            m_new = jnp.maximum(m_prev, m8.max(axis=0, keepdims=True))
            e = jnp.exp2((s_s[c % 2][...] - m_new) * c2)
            e_s[c % 2][...] = e.astype(e_s[0].dtype)
            l_new = e.reshape(size // 8, 8, size).sum(axis=0).sum(
                axis=0, keepdims=True)
            corr = jnp.exp2((m_prev - m_new) * c2)
            l_s[c:c + 1, :] = l_s[c:c + 1, :] * corr + l_new
            m_s[c:c + 1, :] = m_new
            acc_s[c] = acc_s[c] * corr + jnp.dot(
                v_ref[h * D:(h + 1) * D, :], e_s[c % 2][...],
                preferred_element_type=jnp.float32)

        @pl.when(flag & LAST != 0)
        def _close():
            for c in range(C):
                l = l_s[c:c + 1, :]
                o_ref[c * D:(c + 1) * D, :] = (acc_s[c] / l).astype(
                    o_ref.dtype)
                lse_ref[c:c + 1, :] = m_s[c:c + 1, :] * scale + jnp.log(l)

    def q_map(b, h, p, q_of, k_of, flags):
        return b, h, q_of[p]

    def k_map(b, h, p, q_of, k_of, flags):
        return b, k_of[p], h

    def v_map(b, h, p, q_of, k_of, flags):
        return b, h, k_of[p]

    # q, v and o sequence-minor (the module's docstring)
    in_specs = [pl.BlockSpec((None, C * D, size), q_map),
                pl.BlockSpec((None, size, hb * D), k_map),
                pl.BlockSpec((None, hb * D, size), v_map)]
    operands = [q.reshape(B, S, Hkv * G * D).transpose(0, 2, 1),
                k.reshape(B, S, Hkv * D),
                v.reshape(B, S, Hkv * D).transpose(0, 2, 1)]
    if words is not None:
        in_specs.append(pl.BlockSpec(
            (None, words.shape[1] // n, words.shape[2] // n),
            lambda b, h, p, q_of, k_of, flags: (b, q_of[p], k_of[p])))
        operands.append(words)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, Hkv // hb, len(q_of)),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((None, C * D, size), q_map),
                pl.BlockSpec((None, None, C, size),
                             lambda b, h, p, q_of, k_of, flags:
                             (b, h, 0, q_of[p]))],
            scratch_shapes=[pltpu.VMEM((C, size), jnp.float32),
                            pltpu.VMEM((C, size), jnp.float32),
                            pltpu.VMEM((C, D, size), jnp.float32),
                            pltpu.VMEM((size, size), jnp.float32),
                            pltpu.VMEM((size, size), jnp.float32),
                            pltpu.VMEM((size, size), q.dtype),
                            pltpu.VMEM((size, size), q.dtype),
                            pltpu.VMEM((size, size), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((B, Hkv * G * D, S), q.dtype),
                   jax.ShapeDtypeStruct((B, Hkv // hb, C, S), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=min(_VMEM_LIMIT, max(
                32 << 20, 2 * vmem_bytes(q.dtype, size, C, D)))),
        name="attn_fwd_tiles",
    )(jnp.asarray(q_of), jnp.asarray(k_of), jnp.asarray(flags), *operands)
    return (o.transpose(0, 2, 1).reshape(q.shape),
            jnp.einsum("bhgq->bqhg", lse.reshape(B, Hkv, G, S)))
