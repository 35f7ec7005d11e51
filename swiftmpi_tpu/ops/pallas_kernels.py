"""Pallas TPU kernels for the server-side update path.

``adagrad_update`` is the fused server-side optimizer kernel — the
reference's ``apply_push_value`` hot loop (word2vec.h:177-185,
lr.cpp:68-75) as a single VMEM pass:

    accum' = accum + g^2
    param' = param + lr * g * rsqrt(accum' + fudge)

XLA already fuses this chain well; the Pallas version pins the execution
shape — elementwise over a flat ``(rows, 128)`` lane-aligned view with one
VMEM pass per block, and declares input/output aliasing for the pallas
call.  Whether the aliasing actually elides the table copy depends on the
caller: inside the framework's jitted training step the whole table state
is donated (``_build_step`` donates ``state``), so XLA can satisfy
the alias in place; called standalone (as the tests do), the jit keeps its
inputs valid and a copy is inserted.  (The flat view may also cost a
relayout copy for widths that are not lane-aligned; for 128-multiple
embeddings the reshape is layout-free.  The kernel exists as the
framework's optimizer-kernel extension point, not because the jnp rule is
slow.)

On non-TPU backends the kernel runs in Pallas interpret mode (numerics
identical), which the tests use to pin it against the pure-jnp rule.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_DEF_BLOCK_ROWS = 512


def _adagrad_kernel(lr: float, fudge: float, p_ref, a_ref, g_ref,
                    po_ref, ao_ref):
    g = g_ref[:]
    a = a_ref[:] + g * g
    ao_ref[:] = a
    po_ref[:] = p_ref[:] + lr * g * jax.lax.rsqrt(a + fudge)


@functools.partial(jax.jit, static_argnames=("lr", "fudge", "block_rows",
                                             "interpret"))
def adagrad_update(param: jax.Array, accum: jax.Array, grad: jax.Array,
                   lr: float, fudge: float = 1e-6,
                   block_rows: int = _DEF_BLOCK_ROWS,
                   interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """Fused in-place AdaGrad over arbitrarily-shaped (same-shape) arrays."""
    shape, dtype = param.shape, param.dtype
    n = param.size
    block = block_rows * LANES
    padded = pl.cdiv(n, block) * block
    rows = padded // LANES

    def flat(x):
        x = x.reshape(-1)
        if padded != n:
            x = jnp.pad(x, (0, padded - n))
        return x.reshape(rows, LANES)

    p2, a2, g2 = flat(param), flat(accum), flat(grad)
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    po, ao = pl.pallas_call(
        functools.partial(_adagrad_kernel, lr, fudge),
        out_shape=(jax.ShapeDtypeStruct((rows, LANES), dtype),
                   jax.ShapeDtypeStruct((rows, LANES), dtype)),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec),
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(p2, a2, g2)
    return (po.reshape(-1)[:n].reshape(shape),
            ao.reshape(-1)[:n].reshape(shape))


def default_interpret() -> bool:
    """Interpret mode off only on real TPU devices (checked via the
    device platform, not the backend name — see calibration.on_tpu)."""
    from swiftmpi_tpu.ops.calibration import on_tpu

    return not on_tpu()
