"""Operations and bytes one word2vec train step needs, from its shapes.

The copy of ``bench.py::_w2v_step_bytes`` for the reference-parity CBOW
rendering, freed of the model object: rows pulled are read once; every row
pushed reads and writes its field and its f32 AdaGrad accumulator (four row
passes).  Sampling, index arithmetic and loss scalars are negligible next
to row traffic.  It is a count of what the *algorithm* requests, with no
credit for duplicate keys (a Zipf batch touches fewer distinct rows than it
requests) and no charge for anything proportional to the table: that is the
floor a step is held against, not a model of today's program.
"""

from __future__ import annotations


def rows_per_step(centers: int, window: int, negative: int) -> dict:
    """Row requests of one step of ``centers`` center words."""
    targets = centers * (negative + 1)        # h rows: center + K negatives
    contexts = centers * 2 * window           # v rows, padded window
    return {"pulled": targets + contexts, "pushed": targets + contexts}


def step_bytes(centers: int, window: int, negative: int, len_vec: int,
               itemsize: int = 4) -> float:
    """HBM bytes one step must move."""
    rows = rows_per_step(centers, window, negative)
    row = len_vec * itemsize
    return float(rows["pulled"] * row                  # gather
                 + rows["pushed"] * (2 * row + 2 * len_vec * 4))


def step_flops(centers: int, window: int, negative: int,
               len_vec: int) -> float:
    """Floating-point operations of one step: the context sum, both
    contractions over (K+1) targets, the outer-product h gradient, and
    AdaGrad (square, add, rsqrt, multiply, add: 5 a pushed element)."""
    t = centers * (negative + 1)
    c = centers * 2 * window
    return float(c * len_vec                 # neu1
                 + 2 * t * len_vec           # f
                 + 2 * t * len_vec           # neu1e
                 + t * len_vec               # g * neu1
                 + 5 * (t + c) * len_vec)    # AdaGrad


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
