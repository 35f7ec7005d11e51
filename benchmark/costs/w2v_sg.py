"""Operations and bytes one skip-gram + negative-sampling train step needs,
from its shapes.

By ``costs/w2v.py``'s convention: a count of what the *algorithm* requests
on the padded ``(centers, 2 * window)`` pair grid, with no credit for
duplicate keys (a center's ``h`` row is requested once a pair, 2W times a
center, before Zipf adds more), none for pairs the window shrink leaves
dead, and no charge for anything proportional to the table.  Per pair the
step reads one input row ``v[context]`` and ``K + 1`` target rows
``h[center]``, ``h[negative_1..K]``, and pushes a gradient to each of them;
every row pushed reads and writes its field and its f32 AdaGrad accumulator
(four row passes).  Sampling (``K`` draws a pair), index arithmetic and
loss scalars are negligible next to row traffic.  That is the floor a step
is held against, not a model of today's program.
"""

from __future__ import annotations

from . import w2v as cbow


def rows_per_step(centers: int, window: int, negative: int) -> dict:
    """Row requests of one step of ``centers`` center words."""
    pairs = centers * 2 * window              # the padded pair grid
    targets = pairs * (negative + 1)          # h rows: center + K negatives
    return {"pairs": pairs, "pulled": targets + pairs,
            "pushed": targets + pairs}


def step_bytes(centers: int, window: int, negative: int, len_vec: int,
               itemsize: int = 4) -> float:
    """HBM bytes one step must move: every requested row at what
    ``costs/w2v.py`` charges a row that is pulled and pushed once, so the
    byte convention lives in one place."""
    rows = rows_per_step(centers, window, negative)["pulled"]   # == pushed
    return rows * cbow.step_bytes(1, 0, 0, len_vec, itemsize)


def step_flops(centers: int, window: int, negative: int,
               len_vec: int) -> float:
    """Floating-point operations of one step: per pair the contraction
    ``f = v[c] . h[t]`` over ``K + 1`` targets, the ``v`` gradient
    ``sum_t g_t * h[t]``, the outer-product ``h`` gradient ``g_t * v[c]``,
    and AdaGrad (square, add, rsqrt, multiply, add: 5 a pushed element)."""
    rows = rows_per_step(centers, window, negative)
    t = rows["pairs"] * (negative + 1)
    return float(2 * t * len_vec                      # f
                 + 2 * t * len_vec                    # v gradient
                 + t * len_vec                        # g * v[c]
                 + 5 * rows["pushed"] * len_vec)      # AdaGrad


def step_floor_seconds(shape: dict, peaks: dict) -> dict:
    """The least time the chip could take for a step of ``shape``
    (``centers``, ``window``, ``negative``, ``len_vec``, ``chips``), and
    which peak bounds it.  Work is taken as split evenly over the chips."""
    chips = int(shape.get("chips", 1))
    args = (shape["centers"], shape["window"], shape["negative"],
            shape["len_vec"])
    by_bytes = step_bytes(*args) / chips / peaks["hbm_bytes_per_s"]
    by_flops = step_flops(*args) / chips / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "bytes": step_bytes(*args), "flops": step_flops(*args)}
