"""Operations and bytes one block-diffusion train step of the block stack
needs, from its shapes (``Family.step_shape``).

*Useful* work only, as ``costs/lm.py`` counts it: 6 FLOPs (2 forward, 4
backward) for every matrix parameter a *trunk position* meets — there are
two a token, the noised copy and the clean one; the head meets the noised
half alone; an expert's three matrices count once for every pick that lands
on a held expert — plus attention's two products over the pairs the
block-diffusion mask lets see each other, ``S^2 + S Lb`` a sequence (twice a
causal sequence's): a noised block sees itself (``S Lb``) and the clean text
before it (``(S^2 - S Lb) / 2``), a clean position the clean text up to its
block's end (``(S^2 + S Lb) / 2``).  Recomputation is not credited, so the
step's share of this floor is its model-FLOP share.  Bytes: every
parameter's weight, gradient and two AdamW moments read and written once.

``kernels`` gives *executed* work, recomputation included, of the compiler's
grouped-matmul kernel (``ragged_dot``, as ``costs/lm.py`` counts it) and of
blockwise attention's tile loop (``attention``: what
``parallel/ring_attention.py`` multiplies, masked elements of a folded tile
included).
"""

from __future__ import annotations

from . import lm

#: products of one (query tile, key tile) pair a step: QK^T and PV in the
#: forward pass and again in the layer's recomputation; the scores again,
#: dV, dP, dK and dQ in the backward pass
ATTENTION_PRODUCTS_PER_TILE_PAIR = 2 * 2 + 5


def visible_pairs(shape: dict) -> int:
    """(query, key) pairs a sequence's mask lets through."""
    S, Lb = shape["seq_len"], shape["diffusion_block"]
    return S * S + S * Lb


def folded_tile_pairs(shape: dict) -> int:
    """(query tile, key tile) pairs blockwise attention multiplies a
    sequence: with N tiles a half, noisy tile i folds clean tiles 0..i and
    itself, clean tile i clean tiles 0..i."""
    n = shape["seq_len"] // min(shape["attn_block"], shape["seq_len"])
    return n * n + 2 * n


def matrix_params_per_position(shape: dict) -> dict:
    """Matrix parameters one trunk position is multiplied with, by part
    (the head: one *token*, the noised copy)."""
    d, width = shape["d_model"], shape["d_head"]
    picks = shape["top_k"] * shape["held_pick_share"] / 100.0
    L = shape["layers"]
    return {"attention": L * 2.0 * d * width
            * (shape["heads"] + shape["kv_heads"]),      # q, o; k, v
            "route": L * float(d * shape["experts"]),
            "experts": L * picks * 3 * d * shape["d_expert"],
            "head": float(shape["vocab"] * d)}


def attention_score_flops(shape: dict) -> float:
    """Forward + backward FLOPs a step of QK^T and PV over the visible
    pairs: 2 products x 2 flop x pairs x H x Dh forward, twice that
    backward."""
    seqs = shape["tokens"] // shape["seq_len"]
    per_seq_fwd = 2 * 2 * visible_pairs(shape) * shape["heads"] \
        * shape["d_head"]
    return float(3 * per_seq_fwd * seqs * shape["layers"])


def step_flops(shape: dict) -> float:
    per = matrix_params_per_position(shape)
    trunk = 2 * shape["tokens"] * (per["attention"] + per["route"]
                                   + per["experts"])
    return 6.0 * (trunk + shape["tokens"] * per["head"]) \
        + attention_score_flops(shape)


def ragged_dot_work(shape: dict) -> dict:
    """``costs/lm.py``'s count over this stack: every layer has the expert
    layer and routes both copies of a token."""
    return lm.ragged_dot_work({
        **shape, "tokens": 2 * shape["tokens"],
        "kinds": [("attention", "moe")] * shape["layers"]})


def attention_work(shape: dict) -> dict:
    """Executed FLOPs and bytes a step of blockwise attention's tile loop:
    every folded tile pair, every head, ``ATTENTION_PRODUCTS_PER_TILE_PAIR``
    products of ``2 size^2 Dh``; bytes: q, k, v, o (and their gradients)
    once a pass in bf16 — the loop is compute-bound."""
    seqs = shape["tokens"] // shape["seq_len"]
    size = min(shape["attn_block"], shape["seq_len"])
    pairs = folded_tile_pairs(shape) * seqs * shape["layers"]
    per_product = 2.0 * size * size * shape["d_head"] * shape["heads"]
    rows = 2 * shape["tokens"] * shape["layers"] * shape["d_head"]
    qo, kv = rows * shape["heads"], rows * shape["kv_heads"]
    return {"flops": ATTENTION_PRODUCTS_PER_TILE_PAIR * pairs * per_product,
            # forward twice: read q k v, write o; backward: read q k v o do,
            # write dq dk dv
            "bytes": 2.0 * (2 * (2 * qo + 2 * kv) + (4 * qo + 4 * kv))}


def step_floor_seconds(shape: dict, peaks: dict) -> dict:
    """The least time the chip could take for a step of ``shape``, which
    peak bounds it, and the two kernels' own floors."""
    chips = int(shape.get("chips", 1))
    flops, nbytes = step_flops(shape), lm.step_bytes(shape)
    by_flops = flops / chips / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / chips / peaks["hbm_bytes_per_s"]
    kernels = {"ragged_dot": ragged_dot_work(shape),
               "attention": attention_work(shape)}
    for work in kernels.values():
        work["seconds"] = max(work["flops"] / peaks["bf16_flops_per_s"],
                              work["bytes"] / peaks["hbm_bytes_per_s"]) / chips
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "bytes": nbytes, "flops": flops, "kernels": kernels}
