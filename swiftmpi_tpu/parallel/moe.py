"""Expert parallelism: a top-k routed expert layer that is told which
experts it holds and drops nothing.

Nothing to port from the reference (SURVEY.md §2.7 "Not present: EP as
MoE") — but its sharded-parameter-table design has a direct modern
descendant: experts are rows of a parameter table sharded over an
``expert`` mesh axis, and token→expert routing is the same
"key → owning shard → all_to_all → apply → all_to_all back" pattern the
``transfer=tpu`` pull/push backend uses for sparse rows.

One layer, two entries:

* :func:`expert_layer` — one device.  The router scores every token over
  all ``E`` experts and picks ``k``; the device computes the part of the
  result its ``held`` experts give (a range of expert ids; the stacked
  expert weights hold exactly those) and leaves the rest out.  This is
  one chip's share of an expert-parallel layer, run without its exchange.
* :func:`moe_ffn` — the same layer over an ``expert`` mesh axis: picks go
  to the device that owns their expert through ``all_to_all``, are
  computed there by the same grouped products, and come back.

Both route, sort the ``T * k`` picks by expert, and run grouped matrix
products over the held experts' ragged groups (``jax.lax.ragged_dot``;
on a TPU the compiler's own grouped-matmul kernel).  There is no
``(T, E, C)`` dispatch tensor and no capacity: the static bound is the
``T * k`` picks themselves, walked in row chunks up to the last one that
holds a held pick — whole chunks of ``ROW_CHUNK`` rows, then the last one
at the smallest rung of a short ladder of row counts (``_rungs``: a
half, the whole) that holds what is left — so the cost follows
the picks that land
here (``T * k * held / E`` in the mean) and **no pick on a held expert is
ever dropped**, whatever the routing.

Routers: ``"softmax"`` (GShard/Switch: top-k of the softmax, renormalised,
with the load-balance auxiliary loss) and ``"sigmoid_bias"`` (selection on
``sigmoid(logits) + bias`` with ``bias`` a fixed buffer, weights from the
unbiased scores, renormalised over the picks; no auxiliary loss); either
may scale the renormalised weights (``route_scale``).
Experts: SwiGLU (``w_gate`` given) or an ungated pair ``act(x W_in) W_out``
with ``act`` of ``ACTIVATIONS`` (ReLU, or its square).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from swiftmpi_tpu import obs
from swiftmpi_tpu.parallel.collectives import all_to_all

EXPERT_AXIS = "expert"
ROUTERS = ("softmax", "sigmoid_bias")
#: what an ungated expert puts between its two matrices
ACTIVATIONS = {"relu": jax.nn.relu,
               "relu2": lambda v: jnp.square(jax.nn.relu(v))}
#: rows of sorted picks one step of the expert loop computes; the loop
#: ends at the last held pick and its last step is cut to the smallest
#: rung (``_rungs``) that holds what is left, so the cost follows the picks
ROW_CHUNK = 8192


class MoEParams(NamedTuple):
    """Router + stacked expert FFN weights.

    The router scores all ``E`` experts.  The stacked weights' leading
    dim is the number of experts *held*: all ``E`` under :func:`moe_ffn`
    (shard it ``P('expert')`` the same way the sparse table rows shard
    over ``model``), the ``held`` range under :func:`expert_layer`.
    """
    router: jax.Array                     # (d_model, E)
    w_in: jax.Array                       # (held, d_model, d_ff)  up
    w_out: jax.Array                      # (held, d_ff, d_model)  down
    w_gate: Optional[jax.Array] = None    # (held, d_model, d_ff) | ungated
    bias: Optional[jax.Array] = None      # (E,) selection bias, a buffer


class MoEStats(NamedTuple):
    """What one expert layer counted, as f32 scalars."""
    picks: jax.Array        # T * k
    held: jax.Array         # picks that landed on a held expert
    dropped: jax.Array      # held picks no grouped product covered: 0
    walked: jax.Array       # sorted rows the expert loop computed
    load_max_over_mean: jax.Array   # over the held experts' group sizes
    layers: jax.Array       # expert layers summed into this record


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                    dtype=jnp.float32, *, held: Optional[int] = None,
                    gated: bool = False, bias: bool = False,
                    std: float = 0.0, bias_std: float = 0.01) -> MoEParams:
    """``std`` 0 scales each matrix by 1/sqrt(fan-in); ``bias`` draws the
    selection bias N(0, ``bias_std``): seeded and non-zero, so that it
    changes which experts are picked (small against the scores' spread,
    as a bias nudged by a load balancer is)."""
    kr, ki, ko, kg, kb = jax.random.split(key, 5)
    n_held = n_experts if held is None else held
    s_in = std or 1.0 / math.sqrt(d_model)
    s_out = std or 1.0 / math.sqrt(d_ff)
    normal = jax.random.normal
    return MoEParams(
        router=normal(kr, (d_model, n_experts), dtype) * s_in,
        w_in=normal(ki, (n_held, d_model, d_ff), dtype) * s_in,
        w_out=normal(ko, (n_held, d_ff, d_model), dtype) * s_out,
        w_gate=normal(kg, (n_held, d_model, d_ff), dtype) * s_in
        if gated else None,
        bias=normal(kb, (n_experts,), jnp.float32) * bias_std
        if bias else None)


# -- routing -----------------------------------------------------------------

def route(x: jax.Array, router: jax.Array, bias, k: int, kind: str,
          scale: float = 1.0):
    """(T, d) tokens -> ``(sel (T, k) int32, gates (T, k) f32, density
    (E,), density_proxy (E,))``; the normalised weights of a token's picks
    sum to ``scale``.  Scores, top-k and weights are f32 with
    the router product at highest precision: a pick is a discrete choice,
    and an operand rounded to bf16 flips near ties.  The two densities
    are token means whose product is the GShard load-balance loss
    (shards pmean them *before* the product)."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    E = logits.shape[-1]
    if kind == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        top, sel = lax.top_k(scores, k)
        gates = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
    elif kind == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        biased = scores if bias is None else \
            scores + lax.stop_gradient(bias.astype(jnp.float32))
        _, sel = lax.top_k(biased, k)
        top = jnp.take_along_axis(scores, sel, axis=-1)
        gates = top / (top.sum(-1, keepdims=True) + 1e-6)
    else:
        raise ValueError(f"unknown router {kind!r}; have {ROUTERS}")
    if scale != 1.0:
        gates = gates * scale
    density = jax.nn.one_hot(sel, E, dtype=jnp.float32).sum(1).mean(0)
    return sel.astype(jnp.int32), gates, density, scores.mean(0)


# -- the grouped products over the held experts ------------------------------

def _chunk_ffn(wc, xs, wrow, live, gs, act="relu"):
    """One row chunk through the held experts: xs (rows, d) sorted by
    expert, ``gs`` the experts' group sizes inside the chunk, ``wrow`` the
    rows' weights, ``act`` an ungated expert's activation -> (rows, d) f32.
    Rows past the last group are the grouped-matmul kernel's to leave
    unwritten: they are zeroed (``live``) before anything reads them."""
    w_in, w_out, w_gate = wc

    def grouped(a, w):
        y = lax.ragged_dot(a, w, gs, preferred_element_type=jnp.float32)
        return jnp.where(live[:, None], y, 0.0)

    with obs.named_scope("experts"):
        up = grouped(xs, w_in)
        h = ACTIVATIONS[act](up) if w_gate is None else \
            jax.nn.silu(grouped(xs, w_gate)) * up
        y = grouped(h.astype(xs.dtype), w_out)
    with obs.named_scope("route"):
        return y * wrow[:, None]


def _chunk_inputs(lo, rows, src, weight, src_idx, order, starts, ends, dtype):
    """The ``rows`` sorted picks from ``lo`` on: (pick ids, live mask, group
    sizes inside the chunk, gathered rows, their weights)."""
    idx = lax.dynamic_slice(order, (lo,), (rows,))
    live = lo + jnp.arange(rows, dtype=jnp.int32) < ends[-1]
    gs = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)
    xs = jnp.where(live[:, None], src[src_idx[idx]], 0).astype(dtype)
    return idx, live, gs, xs, jnp.where(live, weight[idx], 0.0)


def _rungs(rows: int) -> Tuple[int, ...]:
    """The row counts the walk's last chunk may take, in rising order up to
    ``rows``: a half and the whole of it (one rung where it cannot be
    halved).  Made from ``rows`` alone: one ladder for every layer that
    walks.  A rung below the top is one more copy of the chunk's forward,
    recomputation and backward for every expert layer's body to trace,
    lower and read back from the compile cache — the half alone is 3-4 s
    of the ~57 s of warm set-up in the cell with four such bodies, a
    quarter below it about one more, against a bound of a tenth — which is
    what left the quarters and the eighths out, and not the device, where
    a finer ladder is faster (``scripts/expert_walk_micro.py``; PERF.md
    section 6, PR 53)."""
    return (rows // 2, rows) if rows % 2 == 0 else (rows,)


def _walk(body, carry, n_rows, rows: int):
    """``carry = body(lo, size, carry)`` over the sorted rows below
    ``n_rows``: chunks of ``rows`` (a trip count that follows the routing),
    then the rows that are left as one chunk of the smallest rung that
    holds them.  A rest that needs the top rung is one more turn of the
    loop, so the loop's body is the only copy of a whole chunk."""
    low = _rungs(rows)[:-1]          # the top rung is the loop's own body
    full = n_rows // rows
    left = n_rows - full * rows
    whole = left > (low[-1] if low else 0)
    carry = lax.fori_loop(0, full + whole,
                          lambda c, acc: body(c * rows, rows, acc), carry)
    if not low:
        return carry
    # branch 0 leaves the carry as it is; branch i walks low[i - 1] rows
    fits = jnp.asarray((0,) + low[:-1], jnp.int32)
    return lax.switch(
        jnp.where(whole, 0, (left > fits).sum()),
        [lambda acc: acc] + [partial(body, full * rows, r) for r in low],
        carry)


@partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12))
def _grouped(w, src, weight, src_idx, out_idx, order, starts, ends,
             n_out, compute_dtype, rows, scope_prefix, act):
    """The walk over the sorted rows that hold a held pick: ``(out (n_out,
    d) f32, rows covered, rows walked)``.  Its trip count and its last
    chunk's size follow the routing (:func:`_walk`), which autodiff cannot
    transpose;
    the backward pass below walks the same chunks, recomputing each, so
    neither time nor memory follows the static ``T * k`` bound.
    ``scope_prefix``: the ``obs.scope_prefix`` the layer was traced under,
    for the backward pass, which is traced after that context has closed."""
    dtype = compute_dtype or src.dtype
    with obs.named_scope("experts"):
        wc = jax.tree.map(lambda a: a.astype(dtype), w)

    def body(lo, size, acc):
        out, covered, walked = acc
        with obs.named_scope("route"):
            idx, live, gs, xs, wrow = _chunk_inputs(
                lo, size, src, weight, src_idx, order, starts, ends, dtype)
        y = _chunk_ffn(wc, xs, wrow, live, gs, act)
        with obs.named_scope("route"):
            return (out.at[out_idx[idx]].add(y), covered + gs.sum(),
                    walked + size)

    with obs.named_scope("route"):
        out = jnp.zeros((n_out, src.shape[-1]), jnp.float32)
    return _walk(body, (out, jnp.int32(0), jnp.int32(0)), ends[-1], rows)


def _grouped_fwd(w, src, weight, src_idx, out_idx, order, starts, ends,
                 n_out, compute_dtype, rows, scope_prefix, act):
    out = _grouped(w, src, weight, src_idx, out_idx, order, starts, ends,
                   n_out, compute_dtype, rows, scope_prefix, act)
    return out, (w, src, weight, src_idx, out_idx, order, starts, ends)


def _grouped_bwd(n_out, compute_dtype, rows, scope_prefix, act, res, cot):
    with obs.scope_prefix(scope_prefix):
        return _grouped_bwd_walk(compute_dtype, rows, act, res, cot)


def _grouped_bwd_walk(compute_dtype, rows, act, res, cot):
    w, src, weight, src_idx, out_idx, order, starts, ends = res
    dout = cot[0]
    dtype = compute_dtype or src.dtype
    with obs.named_scope("experts"):
        wc = jax.tree.map(lambda a: a.astype(dtype), w)
        dw = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), w)
    with obs.named_scope("route"):
        dsrc = jnp.zeros(src.shape, jnp.float32)
        dweight = jnp.zeros(weight.shape, jnp.float32)

    def body(lo, size, carry):
        dw, dsrc, dweight = carry
        with obs.named_scope("route"):
            idx, live, gs, xs, wrow = _chunk_inputs(
                lo, size, src, weight, src_idx, order, starts, ends, dtype)
            dy = dout[out_idx[idx]]
        _, pull = jax.vjp(lambda wc, xs, wrow: _chunk_ffn(
            wc, xs, wrow, live, gs, act), wc, xs, wrow)
        dwc, dxs, dwrow = pull(dy)
        with obs.named_scope("experts"):
            dw = jax.tree.map(lambda a, b: a + b.astype(a.dtype), dw, dwc)
        with obs.named_scope("route"):
            dxs = jnp.where(live[:, None], dxs.astype(jnp.float32), 0.0)
            return (dw, dsrc.at[src_idx[idx]].add(dxs),
                    dweight.at[idx].add(jnp.where(live, dwrow, 0.0)))

    dw, dsrc, dweight = _walk(body, (dw, dsrc, dweight), ends[-1], rows)
    ints = tuple(np.zeros(a.shape, jax.dtypes.float0)
                 for a in (src_idx, out_idx, order, starts, ends))
    with obs.named_scope("experts"):
        dw = jax.tree.map(lambda g, a: g.astype(a.dtype), dw, w)
    return (dw, dsrc.astype(src.dtype), dweight.astype(weight.dtype)) + ints


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def _held_experts(p: MoEParams, src, src_idx, out_idx, local_e, weight,
                  n_out: int, compute_dtype=None,
                  row_chunk: int = ROW_CHUNK, act: str = "relu"):
    """``out[out_idx[i]] += weight[i] * expert_{local_e[i]}(src[src_idx[i]])``
    over the picks ``i`` whose ``local_e`` names a held expert
    (``local_e == held`` marks a pick that is not this device's), as
    ``(out (n_out, d) f32, group sizes (held,), rows covered, rows walked)``.

    Picks are sorted by expert, held ones first; the sorted rows are
    walked in chunks of ``row_chunk``, the last one at the smallest rung
    that holds what is left: a chunk gathers its rows, runs the
    grouped products over the parts of the experts' groups that fall in
    it, weights and scatters the result back (:func:`_grouped`)."""
    n_picks = local_e.shape[0]
    n_held = p.w_in.shape[0]
    rows = min(row_chunk, n_picks)
    with obs.named_scope("route"):
        order = jnp.argsort(local_e, stable=True).astype(jnp.int32)
        order = jnp.pad(order, (0, rows))   # a chunk may start at any row
        # (a scatter-add of T * k ones into `held` bins runs one row at a
        # time on the chip; a compare-and-sum does not)
        sizes = (local_e[:, None] == jnp.arange(n_held, dtype=jnp.int32)
                 ).sum(0, dtype=jnp.int32)
        ends = jnp.cumsum(sizes)
    out, covered, walked = _grouped(
        (p.w_in, p.w_out, p.w_gate), src, weight, src_idx, out_idx, order,
        ends - sizes, ends, n_out, compute_dtype, rows,
        obs.current_scope_prefix(), act)
    return out, sizes, covered, walked


def _stats(n_picks: int, sizes, covered, walked) -> MoEStats:
    held = sizes.sum().astype(jnp.float32)
    mean = jnp.maximum(held / sizes.shape[0], 1e-9)
    return MoEStats(picks=jnp.float32(n_picks), held=held,
                    dropped=held - covered.astype(jnp.float32),
                    walked=walked.astype(jnp.float32),
                    load_max_over_mean=sizes.max().astype(jnp.float32)
                    / mean, layers=jnp.float32(1.0))


def _check_act(act: str) -> None:
    if act not in ACTIVATIONS:
        raise ValueError(f"unknown expert activation {act!r}; have "
                         f"{tuple(ACTIVATIONS)}")


def expert_layer(params: MoEParams, x: jax.Array, *, k: int = 2,
                 router: str = "softmax",
                 held: Optional[Tuple[int, int]] = None,
                 compute_dtype=None, row_chunk: int = ROW_CHUNK,
                 route_scale: float = 1.0, act: str = "relu"):
    """The expert layer on one device: ``(y (T, d) f32, aux, MoEStats)``.

    ``held = (lo, hi)`` is the range of expert ids whose weights
    ``params`` stacks (default: all).  Every token is routed over all
    ``E`` experts; picks on experts outside the range contribute nothing
    here — they are another device's part of the sum.  ``act``: an ungated
    expert's activation (``ACTIVATIONS``)."""
    _check_act(act)
    E = params.router.shape[1]
    lo, hi = (0, E) if held is None else held
    if hi - lo != params.w_in.shape[0]:
        raise ValueError(f"held experts {lo}..{hi} but the stacked weights "
                         f"hold {params.w_in.shape[0]}")
    T = x.shape[0]
    with obs.named_scope("route"):
        # a share's routing weights multiply the held experts' outputs
        # only: their gradient is one chip's part of a sum, and followed
        # alone it moves whatever it reaches — the router, or the tokens'
        # own representations — towards picks on the absent experts, which
        # add nothing and so cost nothing.  The router's weights take that
        # part (the caller sums or withholds it); the tokens do not.
        x_route = x if hi - lo == E else lax.stop_gradient(x)
        sel, gates, dens, proxy = route(x_route, params.router, params.bias,
                                        k, router, route_scale)
        aux = (dens * proxy).sum() * E if router == "softmax" \
            else jnp.float32(0.0)
        e = sel.reshape(-1)
        local_e = jnp.where((e >= lo) & (e < hi), e - lo, hi - lo)
        token = jnp.arange(T * k, dtype=jnp.int32) // k
    y, *counts = _held_experts(
        params, x, token, token, local_e, gates.reshape(-1), T,
        compute_dtype, row_chunk, act)
    return y, aux, _stats(T * k, *counts)


def moe_ffn(params: MoEParams, x: jax.Array, mesh: Mesh, *,
            axis: str = EXPERT_AXIS, k: int = 2, router: str = "softmax",
            compute_dtype=None, row_chunk: int = ROW_CHUNK,
            route_scale: float = 1.0, act: str = "relu"
            ) -> Tuple[jax.Array, jax.Array]:
    """The expert layer over a mesh axis.

    ``x``: global ``(T, d_model)`` tokens, sharded ``P(axis)`` on T (dp and
    ep share the axis, the standard layout).  Experts shard ``P(axis)`` on
    E.  Returns ``(y, aux_loss)`` with ``y`` sharded like ``x``.

    Each device routes its tokens, sends every pick to the device that
    holds its expert (``all_to_all``; the buffer to a device is sized for
    all of the sender's ``t * k`` picks, so none can overflow), computes
    the picks it received with the grouped products and sends the results
    back to be weighted and summed per token."""
    _check_act(act)
    n = int(mesh.shape[axis])
    E = params.router.shape[1]
    if E % n:
        raise ValueError(f"experts={E} must divide over axis size {n}")
    T, d = x.shape
    if T % n:
        raise ValueError(f"tokens={T} must divide over axis size {n}")
    per = E // n

    x_spec = P(axis)
    p_spec = MoEParams(router=P(), w_in=P(axis), w_out=P(axis),
                       w_gate=None if params.w_gate is None else P(axis),
                       bias=None if params.bias is None else P())

    @partial(jax.shard_map, mesh=mesh, in_specs=(p_spec, x_spec),
             out_specs=(x_spec, P()), check_vma=False)
    def _moe(p, xl):
        sel, gates, dens, proxy = route(xl, p.router, p.bias, k, router,
                                        route_scale)
        aux = (lax.pmean(dens, axis) * lax.pmean(proxy, axis)).sum() * E \
            if router == "softmax" else jnp.float32(0.0)
        n_picks = xl.shape[0] * k
        e = sel.reshape(-1)
        order = jnp.argsort(e, stable=True)           # by owner, by expert
        owner = e[order] // per
        first = jnp.searchsorted(owner, jnp.arange(n), side="left")
        slot = jnp.arange(n_picks) - first[owner]
        # (n, t*k, ...): one buffer per owner, each long enough for all
        send_x = jnp.zeros((n, n_picks, d), xl.dtype).at[owner, slot].set(
            xl[order // k])
        send_e = jnp.full((n, n_picks), per, jnp.int32).at[
            owner, slot].set(e[order] % per)
        recv_x = all_to_all(send_x, axis, split_axis=0, concat_axis=0)
        recv_e = all_to_all(send_e, axis, split_axis=0, concat_axis=0)
        rows = jnp.arange(n * n_picks, dtype=jnp.int32)
        y, *_counts = _held_experts(
            p, recv_x.reshape(n * n_picks, d), rows, rows,
            recv_e.reshape(-1), jnp.ones((n * n_picks,), jnp.float32),
            n * n_picks, compute_dtype, row_chunk, act)
        back = all_to_all(y.reshape(n, n_picks, d), axis, split_axis=0,
                          concat_axis=0)
        picked = back[owner, slot] * gates.reshape(-1)[order][:, None]
        out = jnp.zeros(xl.shape, jnp.float32).at[order // k].add(picked)
        return out.astype(xl.dtype), aux

    return _moe(params, x)


def moe_ffn_reference(params: MoEParams, x: jax.Array, *, k: int = 2,
                      router: str = "softmax",
                      held: Optional[Tuple[int, int]] = None,
                      route_scale: float = 1.0, act: str = "relu"):
    """Dense single-device golden: every held expert applied to every
    token and masked by the gate.  For tests."""
    E = params.router.shape[1]
    lo, hi = (0, E) if held is None else held
    sel, g, dens, proxy = route(x, params.router, params.bias, k, router,
                                route_scale)
    aux = (dens * proxy).sum() * E if router == "softmax" \
        else jnp.float32(0.0)
    gates = jnp.zeros((x.shape[0], E), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], sel].set(g)[:, lo:hi]
    up = jnp.einsum("td,edf->tef", x, params.w_in)
    h = ACTIVATIONS[act](up) if params.w_gate is None else \
        jax.nn.silu(jnp.einsum("td,edf->tef", x, params.w_gate)) * up
    per_e = jnp.einsum("tef,efd->ted", h, params.w_out)
    return jnp.einsum("te,ted->td", gates, per_e), aux
