"""Pluggable pull/push access methods (server-side op plugins).

TPU-native equivalent of the reference's ``PullAccessMethod`` /
``PushAccessMethod`` plugin pair (`/root/reference/src/parameter/
accessmethod.h:7-35`): an ``AccessMethod`` bundles

* the table schema it needs (parameter fields + optimizer-state fields),
* the initial-value distribution for lazily created rows
  (``init_param``, accessmethod.h:14-16),
* which fields a ``pull`` returns to workers (``get_pull_value`` — e.g.
  word2vec pulls h,v but not the AdaGrad sums, word2vec.h:160-165),
* the pure update rule ``apply_push`` applied to pushed gradients
  (``apply_push_value``).

Where the reference mutates one row behind a pointer, here ``apply_push`` is
a pure, vectorized function over ``(n, d)`` row batches, traceable under
``jit`` and identical per-row math.

Sign convention: like the reference apps, gradients are pushed in the
*ascent* direction and the update **adds** (word2vec.h:177-185,
lr.cpp:68-75).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Initializer = Callable[[jax.Array, Tuple[int, ...]], jax.Array]


def zeros_init(key: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    return jnp.zeros(shape, jnp.float32)


def uniform01_init(key: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    """U(0,1) — the reference LR weight init draws ``gen_float()``
    (lr.cpp:48-50)."""
    return jax.random.uniform(key, shape, jnp.float32)


def vec_rand_init(key: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    """(U(0,1) - 0.5) / dim — the reference ``Vec::randInit`` embedding
    init (vec1.h:229-232)."""
    dim = shape[-1]
    return (jax.random.uniform(key, shape, jnp.float32) - 0.5) / dim


#: lanes of a TPU vector register: the minor dimension of a tiled array
#: occupies a whole number of these
_LANES = 128


def stored_width(width: int) -> int:
    """Lanes a table keeps for a row of ``width`` values: ``width``
    rounded up to whole 128-lane tiles where that costs at most a third
    more memory (300 -> 384, 100 -> 128), else ``width`` itself (a
    one-wide row would pad 128 x; a multiple of 128 pads nothing).

    Why a row is stored wider than it is.  Every pull gathers rows and
    every sparse push scatters them, and the TPU's gather / scatter work
    on a row-major operand.  The compiler's default layout of a tall
    ``(rows, w)`` array is row-major exactly when ``w`` is a multiple of
    128; otherwise it is column-major, and every train step copies the
    WHOLE field into a padded row-major buffer and back (11 copies of
    2.8 GB, ~108 ms a step at 2,340,001 x 300).  Asking for a row-major
    layout instead (``jax.experimental.layout``) does not survive JAX's
    persistent compile cache on jaxlib 0.9.0; a width whose DEFAULT
    layout is the wanted one does, and occupies the same 384 lanes.
    PERF.md section 6, PR 32; ``tests/test_compile_v5e.py``."""
    padded = -(-width // _LANES) * _LANES
    return padded if 3 * padded <= 4 * width else width


@dataclass(frozen=True)
class FieldSpec:
    """One table field.  ``dim`` is the STORED row width; ``width`` the
    number of leading lanes that mean something (default: all of them).
    The lanes beyond ``width`` are zero when the table is built and stay
    zero under any rule that maps a zero gradient to a zero update, so
    rows can be pulled, multiplied and pushed at their stored width."""
    dim: int
    init: Initializer = zeros_init
    dtype: jnp.dtype = jnp.float32
    width: Optional[int] = None

    @property
    def logical(self) -> int:
        return self.dim if self.width is None else self.width

    def draw(self, key: jax.Array, rows: int) -> jax.Array:
        """``(rows, dim)`` initial values: ``init`` over the logical
        width (so the draw does not depend on the padding), zeros beyond."""
        out = self.init(key, (rows, self.logical)).astype(self.dtype)
        pad = self.dim - self.logical
        return jnp.pad(out, ((0, 0), (0, pad))) if pad else out


def row_field(width: int, init: Initializer = zeros_init,
              dtype: jnp.dtype = jnp.float32) -> FieldSpec:
    """A field of ``width``-wide rows, stored as :func:`stored_width`
    says a row of that width should be."""
    return FieldSpec(stored_width(width), init, dtype, width)


class AccessMethod:
    """Base: schema + init + pull view + push rule."""

    #: name -> FieldSpec; the full server-side row (params + optimizer state)
    fields: Dict[str, FieldSpec] = {}
    #: subset of ``fields`` a pull returns (worker-visible view)
    pull_fields: Tuple[str, ...] = ()
    #: gradient entries a push must provide
    grad_fields: Tuple[str, ...] = ()

    def apply_push(self, params: Dict[str, jax.Array],
                   grads: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        """Pure row-batch update: (fields, grads) -> the UPDATED fields
        only.  ``grads`` may carry a subset of ``grad_fields`` — rules
        whose grad is absent are skipped, so a caller can push gradient
        families independently (e.g. word2vec h-grads keyed by target
        slots and v-grads keyed by context slots in separate pushes,
        rather than zero-padding both into one combined batch)."""
        raise NotImplementedError

    def touched_fields(self, grad_fields) -> Tuple[str, ...]:
        """Fields ``apply_push`` READS OR WRITES given these grad entries
        — sparse push paths gather exactly these rows and re-scatter the
        written subset.  An access method whose rule reads a field it
        does not update must include it here, or the row-batched
        ``params`` handed to ``apply_push`` will be missing it."""
        return tuple(self.fields)


@dataclass
class AdaGradRule:
    """One (param, accumulator, grad) triple updated AdaGrad-style."""
    param: str
    accum: str
    grad: str


class AdaGradAccess(AccessMethod):
    """Server-side AdaGrad, the reference's only optimizer.

    Per element (word2vec.h:177-185 / lr.cpp:68-75, fudge_factor 1e-6):
        accum += g^2
        param += lr * g / sqrt(accum + fudge)      # accum already updated
    """

    def __init__(self, learning_rate: float,
                 rules: Tuple[AdaGradRule, ...],
                 fields: Dict[str, FieldSpec],
                 pull_fields: Tuple[str, ...],
                 fudge_factor: float = 1e-6):
        self.learning_rate = float(learning_rate)
        self.rules = tuple(rules)
        self.fields = dict(fields)
        self.pull_fields = tuple(pull_fields)
        self.grad_fields = tuple(r.grad for r in self.rules)
        self.fudge_factor = float(fudge_factor)
        for r in self.rules:
            if r.param not in self.fields or r.accum not in self.fields:
                raise ValueError(f"rule {r} references unknown field")

    def apply_push(self, params, grads):
        out = {}
        for r in self.rules:
            if r.grad not in grads:
                continue
            g = grads[r.grad].astype(jnp.float32)
            accum = params[r.accum] + jnp.square(g)
            out[r.accum] = accum
            p = params[r.param]
            out[r.param] = (p.astype(jnp.float32) + (
                self.learning_rate * g
                * jax.lax.rsqrt(accum + self.fudge_factor))
            ).astype(p.dtype)      # fp32 math, one rounding on store
        return out

    def touched_fields(self, grad_fields):
        gf = set(grad_fields)
        out = []
        for r in self.rules:
            if r.grad in gf:
                out += [r.param, r.accum]
        return tuple(out)


def lr_access(learning_rate: float) -> AdaGradAccess:
    """Logistic-regression row: scalar weight + grad²-sum
    (reference LRParam, lr.cpp:14-22,60-81)."""
    return AdaGradAccess(
        learning_rate,
        rules=(AdaGradRule("val", "grad2sum", "val"),),
        fields={"val": FieldSpec(1, uniform01_init),
                "grad2sum": FieldSpec(1, zeros_init)},
        pull_fields=("val",),
    )


def w2v_access(learning_rate: float, len_vec: int,
               param_dtype=jnp.float32) -> AdaGradAccess:
    """word2vec row: h,v embeddings + per-element AdaGrad sums
    (reference WParam, word2vec.h:32-46,167-191).

    ``param_dtype=bfloat16`` stores the embedding fields at half width —
    on TPU the row gathers/scatters are the measured bottleneck and move
    half the HBM bytes; pulls are upcast to fp32 before any math and the
    AdaGrad accumulators stay fp32 (the update rule computes in fp32 and
    rounds once on store)."""
    return AdaGradAccess(
        learning_rate,
        rules=(AdaGradRule("h", "h2sum", "h"),
               AdaGradRule("v", "v2sum", "v")),
        fields={"h": row_field(len_vec, vec_rand_init, param_dtype),
                "v": row_field(len_vec, vec_rand_init, param_dtype),
                "h2sum": row_field(len_vec),
                "v2sum": row_field(len_vec)},
        pull_fields=("h", "v"),
    )


class SGDAccess(AccessMethod):
    """Plain additive SGD (no accumulator) — not in the reference, but the
    natural second access method and the cheapest push path."""

    def __init__(self, learning_rate: float, fields: Dict[str, FieldSpec],
                 pull_fields: Tuple[str, ...],
                 grad_fields: Tuple[str, ...]):
        self.learning_rate = float(learning_rate)
        self.fields = dict(fields)
        self.pull_fields = tuple(pull_fields)
        self.grad_fields = tuple(grad_fields)

    def apply_push(self, params, grads):
        out = {}
        for name in self.grad_fields:
            if name in grads:
                out[name] = params[name] + self.learning_rate * grads[name]
        return out

    def touched_fields(self, grad_fields):
        return tuple(f for f in self.grad_fields if f in set(grad_fields))
