"""Expert parallelism: top-k routed mixture-of-experts over a mesh axis.

Nothing to port from the reference (SURVEY.md §2.7 "Not present: EP as
MoE") — but its sharded-parameter-table design has a direct modern
descendant: experts are rows of a parameter table sharded over an
``expert`` mesh axis, and token→expert routing is the same
"key → owning shard → all_to_all → apply → all_to_all back" pattern the
``transfer=tpu`` pull/push backend uses for sparse rows.  This module is
that pattern for dense FFN experts (GShard/Switch style):

1. Router: per-token logits over E experts; top-k gating with normalized
   softmax weights + the standard load-balance auxiliary loss.
2. Capacity: each expert processes at most C tokens per device shard
   (static shape, XLA-friendly); overflow tokens are dropped (their
   combine weight is zero — they pass through the residual).
3. Dispatch: one-hot ``(T, E, C)`` dispatch tensor → einsum into per-
   expert buffers → ``all_to_all`` over the ``expert`` axis so each device
   holds *all* shards' tokens for *its* experts → local FFN → reverse
   ``all_to_all`` → weighted combine.

Everything is einsum + two all_to_alls: MXU-shaped, static, fusable.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from swiftmpi_tpu.parallel.collectives import all_to_all

EXPERT_AXIS = "expert"


class MoEParams(NamedTuple):
    """Router + stacked expert FFN weights.

    ``w_in``/``w_out`` leading dim is E (global expert count) — shard it
    ``P('expert')`` the same way the sparse table rows shard over
    ``model``.
    """
    router: jax.Array   # (d_model, E)
    w_in: jax.Array     # (E, d_model, d_ff)
    w_out: jax.Array    # (E, d_ff, d_model)


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                    dtype=jnp.float32) -> MoEParams:
    kr, ki, ko = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    return MoEParams(
        router=jax.random.normal(kr, (d_model, n_experts), dtype) * s_in,
        w_in=jax.random.normal(ki, (n_experts, d_model, d_ff), dtype) * s_in,
        w_out=jax.random.normal(ko, (n_experts, d_ff, d_model), dtype)
        * s_out,
    )


def _top_k_gating(logits: jax.Array, k: int):
    """(T, E) logits -> gates (T, E) with k nonzeros/row (renormalized),
    plus the two per-expert densities whose product is the GShard
    load-balance aux loss: E * sum_e density_e * density_proxy_e.
    The densities are token means, so shards pmean them *before* the
    product — making the distributed aux exactly the global one."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = lax.top_k(probs, k)                    # (T, k)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(T)[:, None], top_idx].set(top_vals)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    density = (gates > 0).astype(probs.dtype).mean(axis=0)     # (E,)
    density_proxy = probs.mean(axis=0)                         # (E,)
    return gates, density, density_proxy


def _dispatch_mask(gates: jax.Array, capacity: int):
    """Turn (T, E) gates into a one-hot (T, E, C) dispatch tensor with
    positions assigned first-come-first-served per expert; tokens beyond
    capacity get an all-zero row (dropped)."""
    assigned = gates > 0                                       # (T, E)
    pos = jnp.cumsum(assigned.astype(jnp.int32), axis=0) - 1   # (T, E)
    keep = assigned & (pos < capacity)
    onehot = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity,
                            dtype=gates.dtype)                 # (T, E, C)
    dispatch = onehot * keep[..., None].astype(gates.dtype)
    combine = dispatch * gates[..., None]
    return dispatch, combine


def moe_ffn(params: MoEParams, x: jax.Array, mesh: Mesh, *,
            axis: str = EXPERT_AXIS, k: int = 2,
            capacity_factor: float = 2.0
            ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE FFN.

    ``x``: global ``(T, d_model)`` tokens, sharded ``P(axis)`` on T (dp and
    ep share the axis, the standard layout).  Experts shard ``P(axis)`` on
    E.  Returns ``(y, aux_loss)`` with ``y`` sharded like ``x``.
    """
    n = int(mesh.shape[axis])
    E = params.router.shape[1]
    if E % n:
        raise ValueError(f"experts={E} must divide over axis size {n}")
    T = x.shape[0]
    if T % n:
        raise ValueError(f"tokens={T} must divide over axis size {n}")
    t_local = T // n
    capacity = max(1, int(math.ceil(t_local * k / E * capacity_factor)))

    x_spec = P(axis)
    p_spec = MoEParams(router=P(), w_in=P(axis), w_out=P(axis))

    @partial(jax.shard_map, mesh=mesh, in_specs=(p_spec, x_spec),
             out_specs=(x_spec, P()), check_vma=False)
    def _moe(p, xl):
        gates, dens, proxy = _top_k_gating(xl @ p.router, k)    # (t, E)
        aux = (lax.pmean(dens, axis) * lax.pmean(proxy, axis)).sum() * E
        dispatch, combine = _dispatch_mask(gates, capacity)     # (t,E,C)
        # per-expert buffers, then route shards->owners over the axis
        buf = jnp.einsum("tec,td->ecd", dispatch, xl)           # (E,C,d)
        buf = all_to_all(buf, axis, split_axis=0, concat_axis=1)
        # now (E/n, n*C, d): all devices' tokens for my experts
        h = jax.nn.relu(jnp.einsum("ecd,edf->ecf", buf, p.w_in))
        out = jnp.einsum("ecf,efd->ecd", h, p.w_out)
        out = all_to_all(out, axis, split_axis=1, concat_axis=0)
        y = jnp.einsum("tec,ecd->td", combine, out)             # (t, d)
        return y, aux

    return _moe(params, x)


def moe_ffn_reference(params: MoEParams, x: jax.Array, *, k: int = 2):
    """Dense single-device golden: every token through its top-k experts,
    no capacity drops.  For tests (capacity_factor high => must match)."""
    gates, dens, proxy = _top_k_gating(x @ params.router, k)
    aux = (dens * proxy).sum() * params.router.shape[1]
    h = jax.nn.relu(jnp.einsum("td,edf->tef", x, params.w_in))
    per_e = jnp.einsum("tef,efd->ted", h, params.w_out)
    return jnp.einsum("te,ted->td", gates, per_e), aux
