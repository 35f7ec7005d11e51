"""Operations and bytes one train step of the state-space hybrid stack needs,
from its shapes (``Family.step_shape``).

*Useful* work only, as ``costs/lm.py`` counts it: 6 FLOPs (2 forward, 4
backward) for every matrix parameter a token meets — a Mamba-2 mixer's input
projection (d x (2 inner + 2 G N + H)) and output projection (inner x d); the
attention layer's four; the router; the shared expert, whole, for every
token; an expert's two matrices once for every pick that lands on a held
expert; the untied head — plus causal attention's two products over the ``S
(S + 1) / 2`` pairs a sequence, plus the chunked scan's four products
(:func:`scan_chunk_flops`) three times (forward, and twice that backward).
Recomputation is not credited, so the step's share of this floor is its
model-FLOP share.  Bytes: every parameter's weight, gradient and two AdamW
moments read and written once.

``kernels`` gives *executed* work, recomputation included (``remat: full``:
forward, the layer's recomputation, and a backward of twice the forward): the
compiler's grouped-matmul kernel (``ragged_dot``, two products an expert),
``ssm_scan`` (the chunked recurrence, by the chunks the program counted),
``ssm_mixer`` (the two projections: what else runs under that scope — the
convolution, activations and the gated norm — counts against the share) and
``full_attention`` (blockwise attention's tile loop at 16 query heads a KV
head, ``costs/swlm.py``'s count).
"""

from __future__ import annotations

from . import lm, swlm
from .mlalm import PROJECTION_PRODUCTS

#: grouped products in one ungated expert's forward (up, down)
RAGGED_PRODUCTS = 2


def mixer_params(shape: dict) -> int:
    """Matrix parameters of one Mamba-2 mixer: in and out projections."""
    d = shape["d_model"]
    inner = shape["ssm_heads"] * shape["ssm_head_dim"]
    groups = 2 * shape["ssm_groups"] * shape["ssm_state"]
    return d * (2 * inner + groups + shape["ssm_heads"]) + inner * d


def attention_params(shape: dict) -> int:
    """Matrix parameters of the attention layer: q, o; k, v."""
    return shape["d_model"] * shape["d_head"] * (
        2 * shape["heads"] + 2 * shape["kv_heads"])


def layer_parameters(shape: dict) -> dict:
    """Every parameter of one layer of each kind, gains, the convolution,
    the per-head vectors and the selection bias included."""
    d = shape["d_model"]
    inner = shape["ssm_heads"] * shape["ssm_head_dim"]
    conv = inner + 2 * shape["ssm_groups"] * shape["ssm_state"]
    return {
        # taps + bias; A_log, dt_bias, D; the gated norm's gain; the layer's
        "ssm": mixer_params(shape) + (shape["kernel"] + 1) * conv
        + 3 * shape["ssm_heads"] + inner + d,
        "full": attention_params(shape) + d,
        "moe": d * shape["experts"] + shape["experts"]
        + 2 * d * shape["d_shared"]
        + shape["experts_held"] * 2 * d * shape["d_expert"] + d}


def parameters(shape: dict) -> int:
    """Every parameter of the cut: what ``init_params`` allocates."""
    per = layer_parameters(shape)
    return sum(per[op if op != "none" else ffn]
               for op, ffn in shape["kinds"]) \
        + 2 * shape["vocab"] * shape["d_model"] + shape["d_model"]


def matrix_params_per_token(shape: dict) -> dict:
    """Matrix parameters one token is multiplied with, by part."""
    d = shape["d_model"]
    picks = shape["top_k"] * shape["held_pick_share"] / 100.0
    out = {"ssm_mixer": 0.0, "attention": 0.0, "route": 0.0, "shared": 0.0,
           "experts": 0.0, "head": float(shape["vocab"] * d)}
    for op, ffn in shape["kinds"]:
        if op == "ssm":
            out["ssm_mixer"] += mixer_params(shape)
        if op == "full":
            out["attention"] += attention_params(shape)
        if ffn == "moe":
            out["route"] += d * shape["experts"]
            out["shared"] += 2 * d * shape["d_shared"]
            out["experts"] += picks * 2 * d * shape["d_expert"]
    return out


def scan_chunk_flops(shape: dict) -> float:
    """FLOPs of one chunk's forward in ``parallel/ssm.py::chunked_scan``:
    with ``Q`` positions a chunk, ``c_t . b_s`` (2 Q^2 N a group), the
    decay-weighted scores times the inputs (2 Q^2 P a head), the chunk's own
    state (2 Q P N a head) and the entering state read out (2 Q P N a
    head)."""
    Q = min(shape["ssm_chunk"], shape["seq_len"])
    H, P, N = shape["ssm_heads"], shape["ssm_head_dim"], shape["ssm_state"]
    return 2.0 * Q * Q * N * shape["ssm_groups"] + 2.0 * Q * Q * P * H \
        + 2 * 2.0 * Q * P * N * H


def attention_score_flops(shape: dict) -> float:
    """Forward + backward FLOPs a step of QK^T and PV on the causal half."""
    seqs = shape["tokens"] // shape["seq_len"]
    S = shape["seq_len"]
    n_attn = sum(op == "full" for op, _ in shape["kinds"])
    return float(3 * 2 * 2 * (S * (S + 1) // 2) * shape["heads"]
                 * shape["d_head"] * seqs * n_attn)


def step_flops(shape: dict) -> float:
    return 6.0 * shape["tokens"] * sum(
        matrix_params_per_token(shape).values()) \
        + attention_score_flops(shape) \
        + 3.0 * shape["scan_chunks"] * scan_chunk_flops(shape)


def scan_work(shape: dict) -> dict:
    """Executed FLOPs and bytes a step under the scope ``ssm_scan``: every
    counted chunk ``PROJECTION_PRODUCTS`` forwards' worth (forward, the
    layer's recomputation, a backward of two); bytes a forward: a chunk's
    inputs and outputs (x and y, B and C, dt) and its state written and read,
    float32 — the least a fused kernel would move; the ``(Q, Q)`` forms the
    plain-XLA rendering writes out count against the share."""
    Q = min(shape["ssm_chunk"], shape["seq_len"])
    H, P, N = shape["ssm_heads"], shape["ssm_head_dim"], shape["ssm_state"]
    per_chunk = 4.0 * (2 * Q * H * P + 2 * Q * shape["ssm_groups"] * N
                       + Q * H + 2 * H * P * N)
    runs = PROJECTION_PRODUCTS * shape["scan_chunks"]
    return {"flops": runs * scan_chunk_flops(shape),
            "bytes": runs * per_chunk}


def mixer_work(shape: dict) -> dict:
    """Executed FLOPs and bytes a step of the mixers' two projections:
    ``PROJECTION_PRODUCTS`` products of 2 x parameters a token; bytes: the
    weights in bf16 and the products' rows (bf16 in, f32 out) once each."""
    n = sum(op == "ssm" for op, _ in shape["kinds"])
    d = shape["d_model"]
    wide = mixer_params(shape) // d        # the two projections' far widths
    products = PROJECTION_PRODUCTS * n
    return {"flops": products * 2.0 * mixer_params(shape) * shape["tokens"],
            "bytes": products * (2.0 * mixer_params(shape)
                                 + shape["tokens"] * (2.0 * 2 * d
                                                      + 4.0 * wide))}


def ragged_dot_work(shape: dict) -> dict:
    """``costs/lm.py``'s count at two products an expert: each expert layer
    runs them forward ``lm.RAGGED_FORWARD_RUNS`` times (the residual takes
    the expert layer's sum as it is, so the layer's recomputation runs none)
    and two transposed products for each in the backward pass."""
    work = lm.ragged_dot_work(shape)
    return {k: v * RAGGED_PRODUCTS / lm.RAGGED_PRODUCTS
            for k, v in work.items()}


def step_floor_seconds(shape: dict, peaks: dict) -> dict:
    """The least time the chip could take for a step of ``shape``, which
    peak bounds it, and the four kernels' own floors."""
    chips = int(shape.get("chips", 1))
    flops, nbytes = step_flops(shape), lm.step_bytes(shape)
    by_flops = flops / chips / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / chips / peaks["hbm_bytes_per_s"]
    kernels = {"ragged_dot": ragged_dot_work(shape),
               "ssm_scan": scan_work(shape),
               "ssm_mixer": mixer_work(shape),
               "full_attention": swlm.attention_work(shape, "full")}
    for work in kernels.values():
        work["seconds"] = max(work["flops"] / peaks["bf16_flops_per_s"],
                              work["bytes"] / peaks["hbm_bytes_per_s"]) / chips
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "bytes": nbytes, "flops": flops, "kernels": kernels}
