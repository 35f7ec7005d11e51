"""Operations and bytes one train step of the sliding-window / full-attention
block stack needs, from its shapes (``Family.step_shape``).

*Useful* work only, as ``costs/lm.py`` counts it: 6 FLOPs (2 forward, 4
backward) for every matrix parameter a token meets — query, output and gate
projections of ``heads x d_head``, key and value of ``kv_heads x d_head``;
the dense FFN; the router; the shared expert, whole, for every token; an
expert's three matrices once for every pick that lands on a held expert; the
untied head — plus attention's two products over the pairs each layer's mask
lets see each other: ``S (S + 1) / 2`` a sequence in a full layer, in a
sliding layer ``w (w + 1) / 2 + (S - w) w`` (a query sees itself and the
``w - 1`` before it).  Recomputation is not credited, so the step's share of
this floor is its model-FLOP share.  Bytes: every parameter's weight,
gradient and two AdamW moments read and written once.

``kernels`` gives *executed* work, recomputation included: the compiler's
grouped-matmul kernel (``ragged_dot``) and blockwise attention's tile loop
under each of the two masks (``window_attention``, ``full_attention``: what
``parallel/ring_attention.py`` multiplies, masked elements of a folded tile
included).
"""

from __future__ import annotations

from . import lm
from .bdlm import ATTENTION_PRODUCTS_PER_TILE_PAIR

#: executions of one grouped product's forward a step.  ``costs/lm.py``
#: counts 2 (the forward pass, the row chunk's recomputation in the expert
#: loop's own backward pass) because nothing in a layer's backward pass reads
#: the expert layer's recomputed output.  Here the residual takes
#: ``RMSNorm(y)``, whose backward pass reads ``y``: the checkpointed layer's
#: recomputation runs the expert loop again (15 ragged-dot calls in a
#: compiled layer body, 3 + 3 forward and 9 backward;
#: ``tests/test_compile_v5e.py``).
RAGGED_FORWARD_RUNS = 3


def visible_pairs(shape: dict, op: str) -> int:
    """(query, key) pairs a sequence's mask lets through in a layer of
    attention kind ``op``."""
    S = shape["seq_len"]
    w = min(shape["window"], S) if op == "sliding" else S
    return w * (w + 1) // 2 + (S - w) * w


def folded_tile_pairs(shape: dict, op: str) -> int:
    """(query tile, key tile) pairs blockwise attention multiplies a
    sequence: query tile ``i`` folds the tiles from the one that holds its
    first query's earliest visible key up to its own."""
    size = min(shape["attn_block"], shape["seq_len"])
    n = shape["seq_len"] // size
    if op != "sliding":
        return n * (n + 1) // 2
    return sum(i + 1 - max(i * size - (shape["window"] - 1), 0) // size
               for i in range(n))


def matrix_params_per_token(shape: dict) -> dict:
    """Matrix parameters one token is multiplied with, by part."""
    d, width = shape["d_model"], shape["d_head"]
    picks = shape["top_k"] * shape["held_pick_share"] / 100.0
    out = {"attention": 0.0, "dense_ffn": 0.0, "route": 0.0, "shared": 0.0,
           "experts": 0.0, "head": float(shape["vocab"] * d)}
    for _op, ffn in shape["kinds"]:
        out["attention"] += d * width * (3 * shape["heads"]       # q, o, gate
                                         + 2 * shape["kv_heads"])  # k, v
        if ffn == "dense":
            out["dense_ffn"] += 3 * d * shape["d_ff"]
        else:
            out["route"] += d * shape["experts"]
            out["shared"] += 3 * d * shape["d_shared"]
            out["experts"] += picks * 3 * d * shape["d_expert"]
    return out


def attention_score_flops(shape: dict) -> float:
    """Forward + backward FLOPs a step of QK^T and PV over the visible
    pairs: 2 products x 2 flop x pairs x H x Dh forward, twice that
    backward."""
    seqs = shape["tokens"] // shape["seq_len"]
    pairs = sum(visible_pairs(shape, op) for op, _ in shape["kinds"])
    return float(3 * 2 * 2 * pairs * shape["heads"] * shape["d_head"] * seqs)


def step_flops(shape: dict) -> float:
    return 6.0 * shape["tokens"] * sum(
        matrix_params_per_token(shape).values()) + attention_score_flops(shape)


def ragged_dot_work(shape: dict) -> dict:
    """``costs/lm.py``'s count with this stack's forward runs."""
    work = lm.ragged_dot_work(shape)
    ratio = (RAGGED_FORWARD_RUNS + 2) / (lm.RAGGED_FORWARD_RUNS + 2)
    return {k: v * ratio for k, v in work.items()}


def attention_work(shape: dict, op: str) -> dict:
    """Executed FLOPs and bytes a step of blockwise attention's tile loop in
    the layers of kind ``op``: every folded tile pair, every head,
    ``ATTENTION_PRODUCTS_PER_TILE_PAIR`` products of ``2 size^2 Dh``; bytes:
    q, k, v, o (and their gradients) once a pass in bf16 — the loop is
    compute-bound."""
    seqs = shape["tokens"] // shape["seq_len"]
    size = min(shape["attn_block"], shape["seq_len"])
    n_layers = sum(o == op for o, _ in shape["kinds"])
    pairs = folded_tile_pairs(shape, op) * seqs * n_layers
    per_product = 2.0 * size * size * shape["d_head"] * shape["heads"]
    rows = shape["tokens"] * n_layers * shape["d_head"]
    qo, kv = rows * shape["heads"], rows * shape["kv_heads"]
    return {"flops": ATTENTION_PRODUCTS_PER_TILE_PAIR * pairs * per_product,
            # forward twice: read q k v, write o; backward: read q k v o do,
            # write dq dk dv
            "bytes": 2.0 * (2 * (2 * qo + 2 * kv) + (4 * qo + 4 * kv))}


def step_floor_seconds(shape: dict, peaks: dict) -> dict:
    """The least time the chip could take for a step of ``shape``, which
    peak bounds it, and the three kernels' own floors."""
    chips = int(shape.get("chips", 1))
    flops, nbytes = step_flops(shape), lm.step_bytes(shape)
    by_flops = flops / chips / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / chips / peaks["hbm_bytes_per_s"]
    kernels = {"ragged_dot": ragged_dot_work(shape),
               "window_attention": attention_work(shape, "sliding"),
               "full_attention": attention_work(shape, "full")}
    for work in kernels.values():
        work["seconds"] = max(work["flops"] / peaks["bf16_flops_per_s"],
                              work["bytes"] / peaks["hbm_bytes_per_s"]) / chips
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "bytes": nbytes, "flops": flops, "kernels": kernels}
