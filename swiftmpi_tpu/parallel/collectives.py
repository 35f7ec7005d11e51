"""Named-axis collective helpers.

The TPU data plane the reference implements with sockets (SURVEY.md §2.8):
thin, uniformly-named wrappers over ``jax.lax`` collectives for use inside
``shard_map`` bodies, plus mesh-level helpers.  Exists mostly so higher
layers (transfer backends, context parallelism) read as communication
patterns — psum / all_gather / reduce_scatter / ppermute / all_to_all —
rather than lax incantations.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def psum(x, axis: str):
    """Dense gradient combine (the reference's server-side add across
    worker pushes, expressed as an ICI all-reduce)."""
    return lax.psum(x, axis)


def pmean(x, axis: str):
    return lax.pmean(x, axis)


def all_gather(x, axis: str, *, tiled: bool = True):
    return lax.all_gather(x, axis, tiled=tiled)


def reduce_scatter(x, axis: str, *, scatter_dimension: int = 0):
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_dimension,
                            tiled=True)


def all_to_all(x, axis: str, split_axis: int, concat_axis: int):
    return lax.all_to_all(x, axis, split_axis, concat_axis, tiled=True)


def ring_permute(x, axis: str, shift: int = 1):
    """Send my block to my +shift neighbor along the ring (the ppermute
    backbone of ring attention)."""
    n = lax.axis_size(axis)
    perm = [(j, (j + shift) % n) for j in range(n)]
    return lax.ppermute(x, axis, perm)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str):
    return lax.axis_size(axis)
