"""Operations and bytes one train step of the latent-attention block stack
with its multi-token-prediction module needs, from its shapes
(``Family.step_shape``).

*Useful* work only, as ``costs/lm.py`` counts it: 6 FLOPs (2 forward, 4
backward) for every matrix parameter a token meets — a latent layer's five
matrices (``W_qa`` d x r_q, ``W_qb`` r_q x H (nope + rope), ``W_kva`` d x
(r_kv + rope), ``W_kvb`` r_kv x H (nope + v), ``W_o`` H v x d); the dense FFN;
the router; the shared expert, whole, for every token; an expert's three
matrices once for every pick that lands on a held expert; the module's merge
(2d x d) and its one more layer; the untied head once for each of the two
losses — plus causal attention's two products over the ``S (S + 1) / 2``
pairs a sequence, in every layer and in the module's.  Recomputation is not
credited, so the step's share of this floor is its model-FLOP share.  Bytes:
every parameter's weight, gradient and two AdamW moments read and written
once.

``kernels`` gives *executed* work, recomputation included: the compiler's
grouped-matmul kernel (``ragged_dot``; the module's expert layer calls it as
the stack's do) and ``latent_attention`` — what runs under the device scope
of that name: blockwise attention's tile loop (what
``parallel/ring_attention.py`` multiplies, masked elements of a folded tile
included) **and** the five projections (forward, the layer's recomputation,
two products backward), in the stack's layers only: the module's layer runs
under ``mtp``.  That makes the scope's share comparable with nothing that
credits tile loops alone (``sw_*_attention_roofline``, ``bd_attention_
roofline``).
"""

from __future__ import annotations

from . import lm
from .bdlm import ATTENTION_PRODUCTS_PER_TILE_PAIR

#: executions of one matrix product of a layer a step under ``remat: full``:
#: the forward pass, the layer's recomputation, and two products (for the
#: input and for the weight) in the backward pass
PROJECTION_PRODUCTS = 4


def layer_kinds(shape: dict) -> list:
    """The stack's layers and, behind them, the module's (the last layer's
    kind) where the configuration has one."""
    return list(shape["kinds"]) + [shape["kinds"][-1]] * shape["mtp"]


def latent_params(shape: dict) -> int:
    """Matrix parameters of one latent attention layer (no gains)."""
    d, H = shape["d_model"], shape["heads"]
    qk = shape["nope"] + shape["rope"]
    return (d * shape["q_rank"] + shape["q_rank"] * H * qk
            + d * (shape["kv_rank"] + shape["rope"])
            + shape["kv_rank"] * H * (shape["nope"] + shape["v_dim"])
            + H * shape["v_dim"] * d)


def parameters(shape: dict) -> int:
    """Every parameter of the cut, gains and the selection bias included:
    what ``init_params`` allocates."""
    d = shape["d_model"]
    attn = latent_params(shape) + shape["q_rank"] + shape["kv_rank"] + 2 * d
    expert = 3 * d * shape["d_expert"]
    moe = (d * shape["experts"] + shape["experts"] + 3 * d * shape["d_shared"]
           + shape["experts_held"] * expert)
    total = 2 * shape["vocab"] * d + d
    for _op, ffn in layer_kinds(shape):
        total += attn + (3 * d * shape["d_ff"] if ffn == "dense" else moe)
    return total + shape["mtp"] * (2 * d * d + 3 * d)


def folded_tile_pairs(shape: dict) -> int:
    """(query tile, key tile) pairs blockwise attention multiplies a
    sequence under the causal mask: the lower triangle of tiles."""
    n = shape["seq_len"] // min(shape["attn_block"], shape["seq_len"])
    return n * (n + 1) // 2


def matrix_params_per_token(shape: dict) -> dict:
    """Matrix parameters one token is multiplied with, by part; ``mtp`` is
    the module's merge, layer and head pass together."""
    d = shape["d_model"]
    picks = shape["top_k"] * shape["held_pick_share"] / 100.0
    moe = {"route": d * shape["experts"], "shared": 3 * d * shape["d_shared"],
           "experts": picks * 3 * d * shape["d_expert"]}
    out = {"latent_attention": 0.0, "dense_ffn": 0.0, "route": 0.0,
           "shared": 0.0, "experts": 0.0, "head": float(shape["vocab"] * d),
           "mtp": 0.0}
    for _op, ffn in shape["kinds"]:
        out["latent_attention"] += latent_params(shape)
        if ffn == "dense":
            out["dense_ffn"] += 3 * d * shape["d_ff"]
        else:
            for k, v in moe.items():
                out[k] += v
    if shape["mtp"]:
        ffn = shape["kinds"][-1][1]
        out["mtp"] = 2 * d * d + latent_params(shape) + shape["vocab"] * d \
            + (3 * d * shape["d_ff"] if ffn == "dense" else sum(moe.values()))
    return out


def attention_score_flops(shape: dict) -> float:
    """Forward + backward FLOPs a step of QK^T (over nope + rope dims) and
    PV (over v dims) on the causal half: 2 flop x pairs x H x width a
    product forward, twice that backward, in every layer and the module's."""
    seqs = shape["tokens"] // shape["seq_len"]
    S = shape["seq_len"]
    width = shape["nope"] + shape["rope"] + shape["v_dim"]
    return float(3 * 2 * (S * (S + 1) // 2) * shape["heads"] * width * seqs
                 * len(layer_kinds(shape)))


def step_flops(shape: dict) -> float:
    return 6.0 * shape["tokens"] * sum(
        matrix_params_per_token(shape).values()) + attention_score_flops(shape)


def ragged_dot_work(shape: dict) -> dict:
    """``costs/lm.py``'s count (two forward runs: the residual takes the
    expert layer's sum as it is, so the layer's recomputation runs none)
    over the stack's expert layers and the module's."""
    return lm.ragged_dot_work({**shape, "kinds": layer_kinds(shape)})


def latent_attention_work(shape: dict) -> dict:
    """Executed FLOPs and bytes a step under the scope ``latent_attention``
    (the stack's layers): the tile loop — every folded tile pair, every head,
    ``ATTENTION_PRODUCTS_PER_TILE_PAIR`` products of ``2 size^2 x 256`` — and
    the five projections ``PROJECTION_PRODUCTS`` times; bytes: q, k, v, o
    (and their gradients) once a pass in bf16 and the projections' weights —
    the scope is compute-bound."""
    seqs = shape["tokens"] // shape["seq_len"]
    size = min(shape["attn_block"], shape["seq_len"])
    n_layers = len(shape["kinds"])
    width = shape["nope"] + shape["rope"]          # == v_dim
    pairs = folded_tile_pairs(shape) * seqs * n_layers
    per_product = 2.0 * size * size * width * shape["heads"]
    rows = shape["tokens"] * n_layers * width * shape["heads"]
    return {"flops": ATTENTION_PRODUCTS_PER_TILE_PAIR * pairs * per_product
            + PROJECTION_PRODUCTS * 2.0 * latent_params(shape)
            * shape["tokens"] * n_layers,
            # forward twice: read q k v, write o; backward: read q k v o do,
            # write dq dk dv; the weights in bf16 once a product
            "bytes": 2.0 * (2 * 4 * rows + 8 * rows)
            + PROJECTION_PRODUCTS * 2.0 * latent_params(shape) * n_layers}


def step_floor_seconds(shape: dict, peaks: dict) -> dict:
    """The least time the chip could take for a step of ``shape``, which
    peak bounds it, and the two kernels' own floors."""
    chips = int(shape.get("chips", 1))
    flops, nbytes = step_flops(shape), lm.step_bytes(shape)
    by_flops = flops / chips / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / chips / peaks["hbm_bytes_per_s"]
    kernels = {"ragged_dot": ragged_dot_work(shape),
               "latent_attention": latent_attention_work(shape)}
    for work in kernels.values():
        work["seconds"] = max(work["flops"] / peaks["bf16_flops_per_s"],
                              work["bytes"] / peaks["hbm_bytes_per_s"]) / chips
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "bytes": nbytes, "flops": flops, "kernels": kernels}
