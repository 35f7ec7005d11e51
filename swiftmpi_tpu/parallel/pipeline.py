"""Pipeline parallelism: GPipe-style microbatched stage pipeline.

The reference has no pipeline parallelism to port (SURVEY.md §2.7 "Not
present: PP") — this is the TPU-native design for it, built the mesh way:

* The model is a chain of ``n_stages`` identical-signature stage functions
  whose parameters are stacked on a leading axis and **row-sharded over a
  ``stage`` mesh axis** — each device (group) holds exactly its stage's
  weights, like the sparse table holds its rows.
* The batch is split into M microbatches.  A ``lax.scan`` runs
  ``M + n_stages - 1`` ticks; at every tick each stage applies its function
  to the activation it currently holds and hands the result to its ``+1``
  neighbour with a single ``ppermute`` hop (ICI neighbour traffic only —
  the same primitive ring attention uses).
* The schedule is expressed with ``lax.scan`` (not ``fori_loop``) so the
  whole pipeline is **differentiable**: ``jax.grad`` through
  ``pipeline_apply`` transposes the scan + ppermute into the reverse
  pipeline schedule automatically — no hand-written backward pass.

Bubble fraction is the classic (n-1)/(M+n-1); pick M >= 4*n for <20%
overhead.  All shapes are static: microbatch count and stage count are
Python ints at trace time, as XLA requires.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from swiftmpi_tpu.parallel.collectives import ring_permute

STAGE_AXIS = "stage"


def stack_stage_params(params_list) -> Any:
    """Stack per-stage parameter pytrees on a new leading ``stage`` axis.

    The result is what ``pipeline_apply`` expects: one pytree whose leaves
    have shape ``(n_stages, ...)``, shardable with ``P('stage', ...)``.
    """
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jax.Array,
                   mesh: Mesh, *, axis: str = STAGE_AXIS,
                   num_microbatches: int) -> jax.Array:
    """Run ``x`` through the stage pipeline; returns the final activation.

    ``stage_fn(params_i, act) -> act`` must keep the activation shape
    (classic homogeneous-pipeline restriction; wrap embed/head layers
    outside the pipelined trunk).  ``stage_params`` leaves have leading dim
    ``n_stages`` and are sharded ``P(axis)``; ``x`` is the global batch
    ``(B, ...)`` with ``B % num_microbatches == 0``.

    The returned array is replicated over ``axis`` (it is psum'd off the
    last stage), so callers can compute the loss without caring where the
    pipeline ended.
    """
    n = int(mesh.shape[axis])
    n_stacked = {leaf.shape[0] for leaf in jax.tree.leaves(stage_params)}
    if n_stacked != {n}:
        raise ValueError(
            f"stage_params leading dims {sorted(n_stacked)} must all equal "
            f"the '{axis}' axis size {n} (one stage per device group)")
    B = x.shape[0]
    if B % num_microbatches:
        raise ValueError(f"batch {B} % microbatches {num_microbatches} != 0")
    mb = B // num_microbatches
    M = num_microbatches

    p_spec = jax.tree.map(lambda _: P(axis), stage_params)

    @partial(jax.shard_map, mesh=mesh, in_specs=(p_spec, P()),
             out_specs=P(), check_vma=False)
    def _pipe(params_l, x_full):
        # params_l leaves: (1, ...) — this device's stage; drop the dim.
        params = jax.tree.map(lambda p: p[0], params_l)
        my = lax.axis_index(axis)
        x_mb = x_full.reshape((M, mb) + x_full.shape[1:])

        state0 = jnp.zeros((mb,) + x_full.shape[1:], x_full.dtype)
        out0 = jnp.zeros_like(x_mb)

        def tick(carry, t):
            state, out = carry
            # stage 0 ingests microbatch t (clamped; masked past M)
            feed = lax.dynamic_index_in_dim(
                x_mb, jnp.minimum(t, M - 1), 0, keepdims=False)
            state = jnp.where((my == 0) & (t < M), feed, state)
            y = stage_fn(params, state)
            # last stage emits microbatch t-(n-1) once warmed up
            slot = jnp.clip(t - (n - 1), 0, M - 1)
            emit = (my == n - 1) & (t >= n - 1)
            cur = lax.dynamic_index_in_dim(out, slot, 0, keepdims=False)
            out = lax.dynamic_update_index_in_dim(
                out, jnp.where(emit, y, cur), slot, 0)
            # hand activations to the +1 neighbour (ring; wraparound into
            # stage 0 is overwritten by the feed next tick)
            state = ring_permute(y, axis)
            return (state, out), None

        (_, out), _ = lax.scan(tick, (state0, out0),
                               jnp.arange(M + n - 1))
        # replicate the result off the last stage
        out = lax.psum(jnp.where(my == n - 1, out, jnp.zeros_like(out)),
                       axis)
        return out.reshape(x_full.shape)

    return _pipe(stage_params, x)


def pipeline_loss(stage_fn: Callable, loss_fn: Callable, stage_params: Any,
                  x: jax.Array, target: Any, mesh: Mesh, *,
                  axis: str = STAGE_AXIS, num_microbatches: int):
    """Convenience: scalar ``loss_fn(final_act, target)`` over the pipeline
    output — the thing to ``jax.grad`` for pipelined training."""
    y = pipeline_apply(stage_fn, stage_params, x, mesh, axis=axis,
                       num_microbatches=num_microbatches)
    return loss_fn(y, target)
