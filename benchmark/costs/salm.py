"""Operations and bytes one train step of the sparse-attention block stack
needs, from its shapes (``Family.step_shape``).

*Useful* work only, as ``costs/lm.py`` counts it: 6 FLOPs (2 forward, 4
backward) for every matrix parameter a token meets — the attention's four
matrices and the indexer's three; an expert's three once for every pick that
lands on a held expert; the head — plus what the mechanism needs beyond its
projections: attention's two products over the **selected** pairs (the
pairs the selection dropped need not be multiplied: that the masked tile walk
multiplies them is this rendering's cost, not the model's) and the index
scores' product over the **causal** pairs (every earlier key is scored, by
definition), each forward once and backward twice.  Recomputation is not
credited, so the step's share of this floor is its model-FLOP share.  Bytes:
every parameter's weight, gradient and two AdamW moments read and written
once.

``kernels`` gives the work the two mechanism scopes are held against and the
grouped-matmul kernel's *executed* work:

* ``sparse_attention`` — the selected pairs' two products (``4 H D`` a pair a
  forward), four forwards' worth: forward, the layer's recomputation, a
  backward of two.  The same work whatever implements it: a rendering that
  stops multiplying unkept pairs reads a higher share of the same count.
* ``indexer`` — one forward and one backward of the index scores over the
  causal pairs (``2 HI dI`` a pair a product; a backward is two products), so
  recomputed walks count against the share.
* ``ragged_dot`` — as ``costs/lm.py`` counts it.
"""

from __future__ import annotations

from . import lm

#: forwards' worth of the selected pairs' two products a step: forward, the
#: layer's recomputation, and a backward of two
ATTENTION_FORWARDS = 4
#: products of the index scores a step that are credited: one forward, and a
#: backward's two (its queries' and its keys' gradients)
INDEX_PRODUCTS = 3


def causal_pairs(shape: dict) -> int:
    """(query, key) pairs ``s <= t`` of one sequence."""
    S = shape["seq_len"]
    return S * (S + 1) // 2


def selected_pairs(shape: dict) -> float:
    """Pairs a sequence's selection keeps in one layer: the counted mean
    keys a query (``sa.selected_keys_per_query``; without a count, the
    closed form ``sum_t min(t + 1, k)``) times its queries."""
    return shape["selected_keys_per_query"] * shape["seq_len"]


def matrix_params_per_token(shape: dict) -> dict:
    """Matrix parameters one token is multiplied with, by part."""
    d, width = shape["d_model"], shape["d_head"]
    picks = shape["top_k"] * shape["held_pick_share"] / 100.0
    L = shape["layers"]
    return {"sparse_attention": L * 2.0 * d * width
            * (shape["heads"] + shape["kv_heads"]),      # q, o; k, v
            "indexer": L * float(d * (shape["index_heads"]
                                      * (shape["index_dim"] + 1)
                                      + shape["index_dim"])),
            "route": L * float(d * shape["experts"]),
            "experts": L * picks * 3 * d * shape["d_expert"],
            "head": float(shape["vocab"] * d)}


def _sequences(shape: dict) -> int:
    return shape["tokens"] // shape["seq_len"]


def attention_forward_flops(shape: dict) -> float:
    """QK^T and PV over the selected pairs, every layer, one forward."""
    return 2 * 2.0 * selected_pairs(shape) * shape["heads"] \
        * shape["d_head"] * _sequences(shape) * shape["layers"]


def index_product_flops(shape: dict) -> float:
    """One product of the index scores over the causal pairs, every
    layer."""
    return 2.0 * causal_pairs(shape) * shape["index_heads"] \
        * shape["index_dim"] * _sequences(shape) * shape["layers"]


def step_flops(shape: dict) -> float:
    return 6.0 * shape["tokens"] * sum(
        matrix_params_per_token(shape).values()) \
        + 3 * attention_forward_flops(shape) \
        + INDEX_PRODUCTS * index_product_flops(shape)


def ragged_dot_work(shape: dict) -> dict:
    """``costs/lm.py``'s count over this stack: every layer has the expert
    layer."""
    return lm.ragged_dot_work({
        **shape, "kinds": [("sparse", "moe")] * shape["layers"]})


def sparse_attention_work(shape: dict) -> dict:
    """FLOPs and bytes a step the scope ``sparse_attention`` is held
    against: the selected pairs' products; bytes: q, k, v, o (and their
    gradients) once a pass in bf16 — compute-bound."""
    rows = shape["tokens"] * shape["layers"] * shape["d_head"]
    qo, kv = rows * shape["heads"], rows * shape["kv_heads"]
    return {"flops": ATTENTION_FORWARDS * attention_forward_flops(shape),
            "bytes": 2.0 * (2 * (2 * qo + 2 * kv) + (4 * qo + 4 * kv))}


def indexer_work(shape: dict) -> dict:
    """FLOPs and bytes a step the scope ``indexer`` is held against: the
    index scores' forward and backward over the causal pairs; bytes: the
    indexer's queries, weights and keys (and their gradients) once a pass —
    compute-bound."""
    per_token = shape["index_heads"] * (shape["index_dim"] + 1) \
        + shape["index_dim"]
    return {"flops": INDEX_PRODUCTS * index_product_flops(shape),
            "bytes": 2.0 * 2 * 2 * shape["tokens"] * shape["layers"]
            * per_token}


def step_floor_seconds(shape: dict, peaks: dict) -> dict:
    """The least time the chip could take for a step of ``shape``, which
    peak bounds it, and the three kernels' own floors."""
    chips = int(shape.get("chips", 1))
    flops, nbytes = step_flops(shape), lm.step_bytes(shape)
    by_flops = flops / chips / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / chips / peaks["hbm_bytes_per_s"]
    kernels = {"ragged_dot": ragged_dot_work(shape),
               "sparse_attention": sparse_attention_work(shape),
               "indexer": indexer_work(shape)}
    for work in kernels.values():
        work["seconds"] = max(work["flops"] / peaks["bf16_flops_per_s"],
                              work["bytes"] / peaks["hbm_bytes_per_s"]) / chips
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "bytes": nbytes, "flops": flops, "kernels": kernels}
