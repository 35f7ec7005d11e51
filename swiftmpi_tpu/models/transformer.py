"""Decoder-only transformer LM: the long-context / multi-axis model family.

The reference stops at shallow embedding models (LR, word2vec, sent2vec —
SURVEY.md §2.5); this model exists so every parallelism axis the framework
provides is exercised by a real trainable model, the way a modern user of
the framework would compose them:

* **dp**   — batch sharded over ``data``; gradient combine is implicit in
  GSPMD (jit over global arrays inserts the psums).
* **tp**   — Megatron-style tensor parallelism via sharding *annotations*
  (``param_shardings``): attention heads and the FFN hidden dim shard over
  ``model``; XLA/GSPMD inserts the all-reduces.  No hand-written
  collectives — the idiomatic TPU expression of TP.
* **sp/cp** — attention runs as ``ring_attention`` / ``ulysses_attention``
  over a ``seq`` axis (parallel/ring_attention.py) for sequences that
  don't fit one chip.
* **pp**   — the block trunk is homogeneous, so it drops into
  ``pipeline_apply`` over a ``stage`` axis (parallel/pipeline.py).
* **ep**   — the FFN can be a routed mixture-of-experts over an ``expert``
  axis (parallel/moe.py).

Architecture: pre-RMSNorm, an output head that is the embedding's transpose
(``tied_head``) or a matrix of its own, and a stack whose layers need not
be alike: each layer has an *operator* (attention with grouped KV heads of
any ``d_head``, optional per-head QK norm and an optional output gate — in
one of the kinds of ``ATTENTION_OPS``: causal with RoPE, causal over a
sliding ``window`` with RoPE, causal with no position embedding, *latent*
attention: queries through a low-rank pair, keys and values re-expanded per
head from one compressed vector a position, RoPE on a decoupled part of the
head whose key every head shares, or learned *sparse* attention
(:func:`_sparse_attention`, parallel/sparse_attention.py): an indexer of
``index_heads`` small heads scores every earlier key, each query attends
its ``index_topk`` best, and the layer's *index loss* — the KL divergence of
the indexer's softmax over the selection from the attention's own
head-averaged probabilities there — teaches the indexer, and it alone
(its input is detached; the selection is a constant of the step) — a gated short
convolution, or a Mamba-2 state-space mixer, :func:`_ssm_mixer`, whose
recurrence is parallel/ssm.py's chunked scan) and an *FFN* (dense
SiLU-gated, or the routed expert layer of parallel/moe.py, beside shared
experts every token runs through where ``n_shared_experts``).  Either may
be ``none``: such a layer is its other half alone, one norm and one
residual, with no parameter for the absent half.  ``layer_ops`` /
``layer_ffns`` name them per layer; runs of equal layers are stacked on a
leading axis and scanned, so a stack compiles one body per run (left empty,
every layer is attention + ``n_experts``'s FFN: the homogeneous stack
``pipeline_apply`` wants).  ``sandwich_norm`` norms each half layer's update
again before the residual takes it; ``embed_scale`` multiplies the embedding
rows.

Objective (``objective``): ``next_token`` — causal attention, cross entropy
of the next token — or ``block_diffusion`` (models/diffusion.py): the trunk
runs ``[x_t ; x_0]``, a noised copy of each sequence before the clean one,
under the block-diffusion attention mask, and the loss is the 1/t-weighted
cross entropy of the masked positions' own tokens.  ``mtp_layers`` 1 puts a
multi-token-prediction module behind a ``next_token`` trunk (:func:`_mtp`):
one more block over the trunk's last hidden state merged with the next
token's embedding, the same head a second time, and ``mtp_weight`` times the
cross entropy of the token after the next added to the loss.

Precision: parameters, residual stream, norms, softmax, router and loss
are ``dtype`` (f32); ``matmul_dtype`` (bf16 in a deployment) is what the
large products' operands are cast to, accumulating in f32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from swiftmpi_tpu import obs
from swiftmpi_tpu.models import diffusion
from swiftmpi_tpu.parallel.moe import (ACTIVATIONS, MoEParams, MoEStats,
                                       expert_layer, init_moe_params,
                                       moe_ffn)
from swiftmpi_tpu.parallel.pipeline import (pipeline_apply,
                                            stack_stage_params)
from swiftmpi_tpu.parallel.ring_attention import (CAUSAL, WindowMask,
                                                  blockwise_attention,
                                                  full_attention,
                                                  ring_attention,
                                                  ulysses_attention)
from swiftmpi_tpu.parallel.sparse_attention import (sparse_attention,
                                                    sparse_attention_dense)
from swiftmpi_tpu.parallel.ssm import chunked_scan, n_chunks


#: attention operators -> (device scope, over ``cfg.window`` only, RoPE).
#: ``full`` is what a stack that mixes it with ``sliding`` layers means by
#: it: every earlier position, and no position embedding at all; ``latent``
#: (:func:`_latent_attention`) rotates ``qk_rope_dim`` of a head's dims;
#: ``sparse`` (:func:`_sparse_attention`) sees the keys its indexer selects
ATTENTION_OPS = {"attention": ("attention", False, True),
                 "sliding": ("window_attention", True, True),
                 "full": ("attention", False, False),
                 "latent": ("latent_attention", False, True),
                 "sparse": ("sparse_attention", False, True)}
#: ``none``: the layer is its other half alone
OPS = (*ATTENTION_OPS, "conv", "ssm", "none")
FFNS = ("dense", "moe", "none")
OBJECTIVES = ("next_token", "block_diffusion")


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    d_ff: int = 512                  # the dense FFN's width
    max_seq: int = 2048
    attention: str = "full"          # full | blockwise | ring | ulysses
    n_experts: int = 0               # 0 => dense SiLU-gated FFN
    moe_top_k: int = 2
    rope_base: float = 10_000.0
    remat: bool = False              # jax.checkpoint each block: trade
                                     # recompute FLOPs for HBM (activation
                                     # memory goes O(L) -> O(1) blocks)
    remat_policy: str = "dots"       # dots: keep projection/FFN matmul
                                     # outputs, recompute only the cheap
                                     # elementwise ops and the S x S
                                     # attention scores (flash-style) —
                                     # the recompute bill drops from
                                     # every-matmul to ~score-matmuls.
                                     # "full": recompute everything.
    dtype: Any = jnp.float32
    # -- the heterogeneous stack ------------------------------------------
    layer_ops: Tuple[str, ...] = ()   # per layer, of OPS; () = attention
    layer_ffns: Tuple[str, ...] = ()  # per layer, of FFNS; () = moe if
                                      # n_experts else dense
    n_kv_heads: int = 0              # 0 => n_heads (MHA)
    qk_norm: bool = False            # RMSNorm over each q / k head
    conv_kernel: int = 3             # the short convolution's taps
    d_expert: int = 0                # an expert's width; 0 => d_ff
    router: str = "softmax"          # parallel/moe.py ROUTERS
    expert_gated: bool = False       # SwiGLU experts (else an ungated pair)
    expert_act: str = "relu"         # ... an ungated pair's activation, of
                                     # parallel/moe.py ACTIVATIONS; the
                                     # shared experts take the experts' form
    experts_held: Tuple[int, int] = ()   # (lo, hi) expert ids held here;
                                         # () = all n_experts
    norm_eps: float = 1e-6
    init_std: float = 0.0            # 0 => 1/sqrt(fan-in) per matrix
    matmul_dtype: Any = None         # operands of the large products
    attn_block: int = 512            # blockwise attention's block
    loss_chunk: int = 0              # tokens a head + loss chunk; 0 = all
    d_head: int = 0                  # a head's width; 0 => d_model / n_heads
    tied_head: bool = True           # the head is the embedding's transpose
    window: int = 0                  # a "sliding" layer's window, in positions
    attn_gate: bool = False          # o * sigmoid(h W_g) before W_o
    sandwich_norm: bool = False      # x + RMSNorm(update), both half layers
    embed_scale: float = 1.0         # x = embed[tok] * embed_scale
    n_shared_experts: int = 0        # experts every token runs beside the
                                     # routed ones, each an expert's width
    d_shared_expert: int = 0         # ... or this width together, if set
    route_scale: float = 1.0         # what a token's routing weights sum to
    # -- a "latent" layer (each 0 until a layer asks for them) -------------
    q_lora_rank: int = 0             # the queries' compressed width
    kv_lora_rank: int = 0            # the keys' and values' compressed width
    qk_nope_dim: int = 0             # a q / k head's dims without position
    qk_rope_dim: int = 0             # ... with RoPE; the key's part is one
                                     # a position, shared by every head
    v_head_dim: int = 0              # a value head's width
    # -- a "sparse" layer (each 0 until a layer asks for them).  It needs
    # attention "blockwise" (or "full": every (S, S) array whole) and the
    # objective "next_token", and there is no multi-token-prediction module
    # of its kind: __post_init__ refuses the rest, naming the field
    index_heads: int = 0             # the indexer's heads
    index_head_dim: int = 0          # ... their width (RoPE over all of it)
    index_topk: int = 0              # keys a query keeps of the earlier ones
    # -- an "ssm" layer (each 0 until a layer asks for them) ---------------
    ssm_heads: int = 0               # heads of the recurrence
    ssm_head_dim: int = 0            # a head's inputs (its state's rows)
    ssm_state: int = 0               # a state's columns
    ssm_groups: int = 0              # B / C groups; divides ssm_heads
    ssm_conv: int = 4                # the causal convolution's taps
    ssm_chunk: int = 128             # positions a chunk of the scan
    # -- the objective ------------------------------------------------------
    objective: str = "next_token"    # of OBJECTIVES
    diffusion_block: int = 4         # block_diffusion: positions a block
    mask_token: int = 0              # ... the id a noised position reads
    noise_eps: float = 1e-3          # ... a block's mask rate t in [eps, 1]
    mtp_layers: int = 0              # next_token: 0 | 1 multi-token-
                                     # prediction modules behind the trunk
    mtp_weight: float = 0.3          # ... its loss's weight in the sum

    def __post_init__(self):
        if self.objective not in OBJECTIVES:
            raise ValueError(f"unknown objective {self.objective!r}; have "
                             f"{OBJECTIVES}")
        if self.objective == "block_diffusion" \
                and self.attention != "blockwise":
            raise ValueError("objective 'block_diffusion' needs attention "
                             f"'blockwise', not {self.attention!r}: the "
                             "other variants are causal")
        self.layer_kinds()           # unknown kinds and counts, by name
        if "sliding" in self.layer_ops:
            if self.window < 1:
                raise ValueError("a 'sliding' layer needs window >= 1, not "
                                 f"{self.window}")
            if self.attention != "blockwise":
                raise ValueError("a 'sliding' layer needs attention "
                                 f"'blockwise', not {self.attention!r}: the "
                                 "other variants see the whole prefix")
            if self.objective != "next_token":
                raise ValueError("a 'sliding' layer brings its own mask; "
                                 f"objective {self.objective!r} brings one "
                                 "for every layer")
        if "latent" in self.layer_ops:
            missing = [f for f in ("q_lora_rank", "kv_lora_rank",
                                   "qk_nope_dim", "qk_rope_dim", "v_head_dim")
                       if getattr(self, f) < 1]
            if missing:
                raise ValueError("a 'latent' layer needs its ranks and head "
                                 f"dims: {', '.join(missing)} not set")
            if self.v_head_dim != self.qk_nope_dim + self.qk_rope_dim:
                raise ValueError(
                    f"a 'latent' layer needs v_head_dim ({self.v_head_dim}) "
                    f"== qk_nope_dim + qk_rope_dim ({self.qk_nope_dim} + "
                    f"{self.qk_rope_dim}): the attention variants take one "
                    "width for keys and values")
            if self.qk_rope_dim % 2:
                raise ValueError("a 'latent' layer rotates pairs: "
                                 f"qk_rope_dim {self.qk_rope_dim} is odd")
        if "sparse" in self.layer_ops:
            missing = [f for f in ("index_heads", "index_head_dim",
                                   "index_topk") if getattr(self, f) < 1]
            if missing:
                raise ValueError("a 'sparse' layer needs its indexer's "
                                 f"sizes: {', '.join(missing)} not set")
            if self.index_head_dim % 2:
                raise ValueError("a 'sparse' layer's indexer rotates pairs: "
                                 f"index_head_dim {self.index_head_dim} is "
                                 "odd")
            if self.attention not in ("blockwise", "full"):
                raise ValueError("a 'sparse' layer needs attention "
                                 f"'blockwise' or 'full', not "
                                 f"{self.attention!r}: the selection is a "
                                 "mask, and the other variants take none")
            if self.objective != "next_token":
                raise ValueError("a 'sparse' layer selects among the earlier "
                                 f"keys; objective {self.objective!r} brings "
                                 "a mask it cannot compose with yet")
            if self.mtp_layers and self.layer_kinds()[-1][0] == "sparse":
                raise ValueError("mtp_layers with a last layer that is "
                                 "'sparse': the module carries no index loss")
        if "ssm" in self.layer_ops:
            missing = [f for f in ("ssm_heads", "ssm_head_dim", "ssm_state",
                                   "ssm_groups") if getattr(self, f) < 1]
            if missing:
                raise ValueError("an 'ssm' layer needs its sizes: "
                                 f"{', '.join(missing)} not set")
            if self.ssm_heads % self.ssm_groups:
                raise ValueError(
                    f"an 'ssm' layer's heads ({self.ssm_heads}) must be a "
                    f"multiple of its groups ({self.ssm_groups}): a group's "
                    "B and C serve whole heads")
            if self.ssm_conv < 1 or self.ssm_chunk < 1:
                raise ValueError("an 'ssm' layer needs ssm_conv >= 1 and "
                                 f"ssm_chunk >= 1, not {self.ssm_conv} and "
                                 f"{self.ssm_chunk}")
            if self.objective != "next_token":
                raise ValueError("an 'ssm' layer's recurrence is causal; "
                                 f"objective {self.objective!r} brings a "
                                 "mask for every layer")
        if self.expert_act not in ACTIVATIONS:
            raise ValueError(f"unknown expert_act {self.expert_act!r}; have "
                             f"{tuple(ACTIVATIONS)}")
        if self.mtp_layers not in (0, 1):
            raise ValueError(f"mtp_layers is 0 or 1, not {self.mtp_layers}: "
                             "one module, one token further")
        if self.mtp_layers and self.objective != "next_token":
            raise ValueError("mtp_layers needs objective 'next_token', not "
                             f"{self.objective!r}: the module predicts the "
                             "token after the next")

    @property
    def head_dim(self) -> int:
        if self.d_head:
            return self.d_head
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(operator, FFN) of every layer."""
        ops = self.layer_ops or ("attention",) * self.n_layers
        ffns = self.layer_ffns or (
            ("moe" if self.n_experts else "dense"),) * self.n_layers
        if len(ops) != self.n_layers or len(ffns) != self.n_layers:
            raise ValueError(f"{self.n_layers} layers, {len(ops)} "
                             f"operators, {len(ffns)} FFNs")
        bad = [x for x in ops if x not in OPS] + \
            [x for x in ffns if x not in FFNS]
        if bad:
            raise ValueError(f"unknown layer kinds {bad}")
        empty = [i for i, kind in enumerate(zip(ops, ffns))
                 if kind == ("none", "none")]
        if empty:
            raise ValueError(f"layers {empty} have neither an operator nor "
                             "an FFN")
        return tuple(zip(ops, ffns))

    def layer_groups(self):
        """Runs of equal layers, in order: [((op, ffn), count)]."""
        runs = []
        for kind in self.layer_kinds():
            if runs and runs[-1][0] == kind:
                runs[-1][1] += 1
            else:
                runs.append([kind, 1])
        return [(kind, n) for kind, n in runs]

    @property
    def heterogeneous(self) -> bool:
        return bool(self.layer_ops or self.layer_ffns)

    @property
    def held(self) -> Tuple[int, int]:
        return tuple(self.experts_held) or (0, self.n_experts)

    @property
    def shared_width(self) -> int:
        """The shared experts' width together (0: none)."""
        return self.d_shared_expert or \
            self.n_shared_experts * (self.d_expert or self.d_ff)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim


# -- params ----------------------------------------------------------------

def _init_block(k, cfg: TransformerConfig, op: str, ffn: str):
    ks = iter(jax.random.split(k, 10))
    d = cfg.d_model

    def mat(rows, cols):
        s = cfg.init_std or 1.0 / math.sqrt(rows)
        return jax.random.normal(next(ks), (rows, cols), cfg.dtype) * s

    # a half's gains exist where the half does
    blk = {f"ln{i}{post}": jnp.ones((d,), cfg.dtype)
           for i, half in ((1, op), (2, ffn)) if half != "none"
           for post in (("", "_post") if cfg.sandwich_norm else ("",))}
    if op == "ssm":
        blk.update(_init_ssm(mat, next(ks), cfg))
    elif op == "latent":
        H, r_q, r_kv = cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank
        blk.update(
            wq_a=mat(d, r_q), q_a_norm=jnp.ones((r_q,), cfg.dtype),
            wq_b=mat(r_q, H * (cfg.qk_nope_dim + cfg.qk_rope_dim)),
            # the compressed keys / values and, beside them, the one rope
            # key a position: no head has rope columns of its own
            wkv_a=mat(d, r_kv + cfg.qk_rope_dim),
            kv_a_norm=jnp.ones((r_kv,), cfg.dtype),
            wkv_b=mat(r_kv, H * (cfg.qk_nope_dim + cfg.v_head_dim)),
            wo=mat(H * cfg.v_head_dim, d))
    elif op in ATTENTION_OPS:
        Dh = cfg.head_dim
        blk.update(wq=mat(d, cfg.n_heads * Dh), wk=mat(d, cfg.kv_heads * Dh),
                   wv=mat(d, cfg.kv_heads * Dh), wo=mat(cfg.n_heads * Dh, d))
        if cfg.qk_norm:
            blk.update(q_norm=jnp.ones((Dh,), cfg.dtype),
                       k_norm=jnp.ones((Dh,), cfg.dtype))
        if cfg.attn_gate:
            blk.update(wg=mat(d, cfg.n_heads * Dh))
        if op == "sparse":
            blk.update(_init_indexer(jax.random.fold_in(k, 1), cfg))
    elif op == "conv":
        blk.update(conv_in=mat(d, 3 * d), conv_out=mat(d, d),
                   conv_w=jax.random.normal(
                       next(ks), (cfg.conv_kernel, d), cfg.dtype)
                   * (cfg.init_std or 1.0 / math.sqrt(cfg.conv_kernel)))
    if ffn == "moe":
        lo, hi = cfg.held
        width = cfg.d_expert or cfg.d_ff
        blk["moe"] = init_moe_params(
            next(ks), d, width, cfg.n_experts, cfg.dtype,
            held=hi - lo, gated=cfg.expert_gated,
            bias=cfg.router == "sigmoid_bias", std=cfg.init_std)
        if cfg.n_shared_experts:
            h = cfg.shared_width
            if cfg.expert_gated:
                blk.update(shared_gate=mat(d, h))
            blk.update(shared_up=mat(d, h), shared_down=mat(h, d))
    elif ffn == "dense":
        h = cfg.d_ff
        blk.update(w_gate=mat(d, h), w_up=mat(d, h), w_down=mat(h, d))
    return blk


def _init_indexer(key, cfg: TransformerConfig) -> dict:
    """A sparse layer's indexer: queries for ``index_heads`` heads, one key
    a position with its LayerNorm, and a weight a head, all from the layer's
    normed input.  Drawn from a key of its own (the layer's other matrices
    draw as every attention layer's do)."""
    d, HI, dI = cfg.d_model, cfg.index_heads, cfg.index_head_dim
    std = cfg.init_std or 1.0 / math.sqrt(d)
    kq, kk, kw = jax.random.split(key, 3)
    return {"wq_idx": jax.random.normal(kq, (d, HI * dI), cfg.dtype) * std,
            "wk_idx": jax.random.normal(kk, (d, dI), cfg.dtype) * std,
            "w_idx": jax.random.normal(kw, (d, HI), cfg.dtype) * std,
            "idx_ln_g": jnp.ones((dI,), cfg.dtype),
            "idx_ln_b": jnp.zeros((dI,), cfg.dtype)}


def _init_ssm(mat, key, cfg: TransformerConfig) -> dict:
    """A Mamba-2 mixer's parameters at their published start: every head's
    decay rate ``-exp(A_log)`` uniform in [-16, -1], its step
    ``softplus(dt_bias)`` log-uniform in [1e-3, 1e-1] (floored at 1e-4),
    its skip ``D`` 1, the gated norm's gain 1; the depthwise convolution's
    taps and bias uniform in +-1/sqrt(taps), whatever ``init_std`` (a
    channel's fan-in is its taps: with taps of ``init_std`` 0.02 the
    recurrence's inputs are ~0.02 and the ``D`` skip is all the mixer
    computes)."""
    H, inner = cfg.ssm_heads, cfg.ssm_inner
    conv = inner + 2 * cfg.ssm_groups * cfg.ssm_state
    k_conv, k_bias, k_a, k_dt = jax.random.split(key, 4)
    bound = 1.0 / math.sqrt(cfg.ssm_conv)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        k_dt, (H,), jnp.float32, math.log(1e-3), math.log(1e-1))), 1e-4)
    return {
        # -> [z | x B C | dt]
        "ssm_in": mat(cfg.d_model, inner + conv + H),
        "ssm_out": mat(inner, cfg.d_model),
        "ssm_conv_w": jax.random.uniform(k_conv, (cfg.ssm_conv, conv),
                                         cfg.dtype, -bound, bound),
        "ssm_conv_b": jax.random.uniform(k_bias, (conv,), cfg.dtype, -bound,
                                         bound),
        "A_log": jnp.log(jax.random.uniform(k_a, (H,), jnp.float32, 1.0,
                                            16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1(dt)
        "D": jnp.ones((H,), jnp.float32),
        "ssm_norm": jnp.ones((inner,), cfg.dtype)}


def init_params(key, cfg: TransformerConfig) -> Dict[str, Any]:
    """Block params are stacked on a leading (n_layers) axis — the layout
    both ``lax.scan`` over layers and ``pipeline_apply`` want.  A
    heterogeneous stack (``layer_ops`` / ``layer_ffns`` given) holds a
    tuple of such stacks, one per run of equal layers
    (``cfg.layer_groups()``)."""
    k_emb, k_blk = jax.random.split(key)
    keys = iter(jax.random.split(k_blk, cfg.n_layers))
    groups = tuple(
        stack_stage_params([_init_block(next(keys), cfg, *kind)
                            for _ in range(n)])
        for kind, n in cfg.layer_groups())

    def vocab_matrix(k):
        return jax.random.normal(k, (cfg.vocab_size, cfg.d_model), cfg.dtype) \
            * (cfg.init_std or 1.0 / math.sqrt(cfg.d_model))

    params = {
        "embed": vocab_matrix(k_emb),
        "blocks": groups if cfg.heterogeneous else groups[0],
        "ln_f": jnp.ones((cfg.d_model,), cfg.dtype),
    }
    if not cfg.tied_head:      # (V, d) like the embedding; a key of its own
        params["head"] = vocab_matrix(jax.random.fold_in(k_emb, 1))
    if cfg.mtp_layers:
        # one block of the last layer's kind (a stack of one: the layers'
        # sharding rules and scan read it as they read a run) between a
        # merge of [hidden ; next token's embedding] and a gain of its own;
        # embedding and head are the trunk's
        k_eh, k_b = jax.random.split(jax.random.fold_in(k_blk, 1))
        d = cfg.d_model
        params["mtp"] = {
            "hnorm": jnp.ones((d,), cfg.dtype),
            "enorm": jnp.ones((d,), cfg.dtype),
            "eh_proj": jax.random.normal(k_eh, (2 * d, d), cfg.dtype)
            * (cfg.init_std or 1.0 / math.sqrt(2 * d)),
            "block": stack_stage_params(
                [_init_block(k_b, cfg, *cfg.layer_kinds()[-1])]),
            "norm": jnp.ones((d,), cfg.dtype)}
    return params


def head_matrix(params, cfg: TransformerConfig):
    """The output head as a (V, d) matrix, ``logits = x @ head.T``: the
    embedding when ``cfg.tied_head``, else ``params["head"]``."""
    return params["embed"] if cfg.tied_head else params["head"]


def is_buffer(path) -> bool:
    """True for a leaf of ``params`` that is a fixed buffer, not a trained
    weight (the router's selection bias): the optimizer leaves it alone."""
    return any(getattr(k, "name", None) == "bias" for k in path)


def is_frozen(path, cfg: TransformerConfig) -> bool:
    """True for a leaf the optimizer must not move: a buffer, and — where
    this device holds a share of the experts and runs without its exchange
    — the router.  A router is replicated over the chips that share its
    layer and its gradient is the sum of every chip's part.  One chip's part
    alone is no descent direction: the absent experts add nothing and so
    cost nothing, and following it sends the picks away from the held
    experts (measured: 25 % of the picks on 8 of 32 experts fell to ~2 % in
    15 steps, PERF.md section 6, PR 31).  The gradient is still computed
    (AdamW's moments see it); only the update is withheld.  For the same
    reason ``parallel/moe.py::expert_layer`` lets no gradient reach a
    share's tokens through the routing weights."""
    share = cfg.n_experts and cfg.held != (0, cfg.n_experts)
    return is_buffer(path) or (share and any(
        getattr(k, "name", None) == "router" for k in path))


def param_shardings(params, cfg: TransformerConfig, mesh: Mesh,
                    *, model_axis: str = "model",
                    data_axis: str = "data") -> Any:
    """Megatron-style TP as GSPMD annotations: FFN hidden dim and QKV/O
    head dim shard over ``model_axis``; embeddings shard rows over it.
    Returns a NamedSharding pytree matching ``params``."""
    del data_axis  # params are never dp-sharded; activations are

    def spec(path: str, leaf) -> P:
        if path in ("wq", "wk", "wv", "wg", "w_gate", "w_up", "shared_gate",
                    "shared_up", "wq_b", "wkv_b"):
            # (L, d, d|dff) col-shard; a latent layer's up-projections by
            # head (its down-projections wq_a / wkv_a and the module's
            # eh_proj are small and replicated: the rope key is every head's)
            return P(None, None, model_axis)
        if path in ("wo", "w_down", "shared_down"):
            return P(None, model_axis, None)      # (L, dff|d, d) row-shard
        if path == "w_in":
            return P(None, None, None, model_axis)   # (L, E, d, dff)
        if path == "w_out":
            return P(None, None, model_axis, None)   # (L, E, dff, d)
        if path in ("embed", "head"):
            return P(model_axis, None)
        return P()

    def walk(tree, name=""):
        if tree is None:
            return None
        if isinstance(tree, MoEParams):      # its w_gate shards like w_in
            return MoEParams(*(walk(v, "w_in" if f == "w_gate" else f)
                               for f, v in zip(tree._fields, tree)))
        if isinstance(tree, dict):
            return {k: walk(v, k) for k, v in tree.items()}
        if isinstance(tree, tuple):
            return tuple(walk(v, name) for v in tree)
        return NamedSharding(mesh, spec(name, tree))

    return walk(params)


# -- forward ---------------------------------------------------------------

def _rms_norm(x, g, eps):
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (x32 * r).astype(x.dtype) * g


def _mm(a, w, cfg: TransformerConfig):
    """``a @ w`` with the operands in ``cfg.matmul_dtype`` and an f32
    accumulator (as they are when it is None)."""
    if cfg.matmul_dtype is None:
        return a @ w
    return jnp.dot(a.astype(cfg.matmul_dtype), w.astype(cfg.matmul_dtype),
                   preferred_element_type=jnp.float32).astype(a.dtype)


def _rope(x, base: float, positions):
    """(B, S, H, D) rotary position embedding (rotate-half pairing) at the
    position ids ``positions`` (S,) f32."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None] * freqs[None]                     # (S, h)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    rot1 = x1 * cos[None, :, None] - x2 * sin[None, :, None]
    rot2 = x2 * cos[None, :, None] + x1 * sin[None, :, None]
    return jnp.concatenate([rot1, rot2], -1).astype(x.dtype)


def _post_norm(y, blk, gain: str, cfg: TransformerConfig):
    """A half layer's update ``y``, normed again before the residual takes
    it where the stack has sandwich norms (``blk`` holds ``gain``)."""
    return _rms_norm(y, blk[gain], cfg.norm_eps) if gain in blk else y


def _attend(q, k, v, cfg: TransformerConfig, mesh: Optional[Mesh],
            seq_axis: str, mask):
    """``softmax(q k^T / sqrt(D) + mask) v`` by ``cfg.attention``'s variant:
    q (B, S, H, D), k and v (B, S, Hkv, D) -> (B, S, H, D)."""
    H, Hkv = q.shape[2], k.shape[2]
    if mask is not CAUSAL and cfg.attention != "blockwise":
        raise ValueError(f"attention {cfg.attention!r} is causal; a mask "
                         "needs 'blockwise'")
    if cfg.matmul_dtype is not None:
        q, k, v = (t.astype(cfg.matmul_dtype) for t in (q, k, v))
    # like _ffn: the collective variants need their axis on the mesh;
    # otherwise fall back to the numerically identical local computation
    has_seq = mesh is not None and seq_axis in mesh.axis_names
    if cfg.attention == "blockwise":
        return blockwise_attention(q, k, v, block=cfg.attn_block, mask=mask)
    if Hkv != H:      # the golden and the ring take one K/V per head
        k, v = (jnp.repeat(t, H // Hkv, axis=2) for t in (k, v))
    if cfg.attention == "ring" and has_seq:
        return ring_attention(q, k, v, mesh, axis=seq_axis, causal=True)
    if cfg.attention == "ulysses" and has_seq:
        return ulysses_attention(q, k, v, mesh, axis=seq_axis, causal=True)
    return full_attention(q, k, v, causal=True)


def _qkv(blk, h, cfg: TransformerConfig, positions, rotary: bool):
    """The normed input ``h`` (B, S, d) -> q (B, S, H, Dh), k and v (B, S,
    Hkv, Dh): projections, QK norm, RoPE at ``positions`` ((S,) f32;
    ``None``: ``0..S-1``) where ``rotary``."""
    B, S, _d = h.shape
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q = _mm(h, blk["wq"], cfg).reshape(B, S, H, Dh)
    k = _mm(h, blk["wk"], cfg).reshape(B, S, Hkv, Dh)
    v = _mm(h, blk["wv"], cfg).reshape(B, S, Hkv, Dh)
    if cfg.qk_norm:
        q = _rms_norm(q, blk["q_norm"], cfg.norm_eps)
        k = _rms_norm(k, blk["k_norm"], cfg.norm_eps)
    if rotary:
        if positions is None:
            positions = jnp.arange(S, dtype=jnp.float32)
        q = _rope(q, cfg.rope_base, positions)
        k = _rope(k, cfg.rope_base, positions)
    return q, k, v


def _attn_out(blk, x, h, o, cfg: TransformerConfig):
    """The heads' outputs ``o`` (B, S, H, Dh) -> the layer's output: the
    gate where the stack has one, ``W_o``, the residual."""
    B, S, _d = x.shape
    o = o.reshape(B, S, -1).astype(x.dtype)
    if cfg.attn_gate:
        o = o * jax.nn.sigmoid(_mm(h, blk["wg"], cfg))
    return x + _post_norm(_mm(o, blk["wo"], cfg), blk, "ln1_post", cfg)


def _attention(blk, x, cfg: TransformerConfig, mesh: Optional[Mesh],
               seq_axis: str, positions=None, mask=CAUSAL, rotary=True):
    """Attention at the position ids ``positions`` ((S,) f32; default
    ``0..S-1``; unused without ``rotary``) under ``mask``
    (``blockwise_attention``'s contract; default causal).  The layer does
    not know the objective: whoever builds another input than a plain
    sequence says where its positions stand and who sees whom
    (``diffusion.attention_inputs``)."""
    h = _rms_norm(x, blk["ln1"], cfg.norm_eps)
    q, k, v = _qkv(blk, h, cfg, positions, rotary)
    return _attn_out(blk, x, h, _attend(q, k, v, cfg, mesh, seq_axis, mask),
                     cfg)


class IndexStats(NamedTuple):
    """What the sparse layers of a step add beside their outputs, summed
    over them."""
    loss: jax.Array        # their index losses, f32
    kept: jax.Array        # (query, key) pairs their selections kept, int32


def _no_index_stats() -> IndexStats:
    return IndexStats(jnp.float32(0.0), jnp.int32(0))


def _layer_norm(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g + b


def _indexer(blk, h, cfg: TransformerConfig, positions):
    """The indexer's three inputs to the index scores, from the *detached*
    normed input ``h`` (B, S, d): ``qi = RoPE(h W_qI)`` (B, S, HI, dI), the
    weights ``w = h W_w / sqrt(HI) / sqrt(dI)`` (B, S, HI) — the scores' two
    scales folded in — and ``ki = RoPE(LayerNorm(h W_kI))`` (B, S, dI), one
    key a position for every head.  RoPE rotates all ``dI`` dims."""
    B, S, _d = h.shape
    HI, dI = cfg.index_heads, cfg.index_head_dim
    h = jax.lax.stop_gradient(h)
    qi = _rope(_mm(h, blk["wq_idx"], cfg).reshape(B, S, HI, dI),
               cfg.rope_base, positions)
    ki = _layer_norm(_mm(h, blk["wk_idx"], cfg), blk["idx_ln_g"],
                     blk["idx_ln_b"], cfg.norm_eps)
    ki = _rope(ki[:, :, None], cfg.rope_base, positions)[:, :, 0]
    w = _mm(h, blk["w_idx"], cfg).astype(jnp.float32) / math.sqrt(HI * dI)
    return qi, w, ki


def _sparse_attention(blk, x, cfg: TransformerConfig, positions=None,
                      mask=CAUSAL):
    """Learned sparse attention (parallel/sparse_attention.py has the
    equations) -> (the layer's output, :class:`IndexStats` of this layer,
    what the selection was made from and the selection: the indexer's
    ``qi``, ``w``, ``ki`` as the index scores' product takes them and the
    packed ``bits``, for :func:`sparse_probe`).
    Queries, keys and values as :func:`_attention` makes them; the indexer
    (:func:`_indexer`) reads the normed input detached, so the language-model
    loss trains everything but the indexer and the index loss the indexer
    alone.  Device scopes: ``indexer`` (its projections, every walk of the
    index scores, the KL), inside it ``index_select`` (the top
    ``index_topk``), the rest under the layer's ``sparse_attention``."""
    if mask is not CAUSAL:
        raise ValueError("a 'sparse' layer selects among the earlier keys; "
                         "it composes with no other mask")
    if positions is None:
        positions = jnp.arange(x.shape[1], dtype=jnp.float32)
    h = _rms_norm(x, blk["ln1"], cfg.norm_eps)
    q, k, v = _qkv(blk, h, cfg, positions, True)
    with obs.named_scope("indexer"):
        qi, w, ki = _indexer(blk, h, cfg, positions)
    if cfg.matmul_dtype is not None:
        q, k, v, qi, ki = (t.astype(cfg.matmul_dtype)
                           for t in (q, k, v, qi, ki))
    if cfg.attention == "blockwise":
        o, loss, kept, bits = sparse_attention(q, k, v, qi, w, ki,
                                               topk=cfg.index_topk,
                                               block=cfg.attn_block)
    else:
        o, loss, kept = sparse_attention_dense(q, k, v, qi, w, ki,
                                               topk=cfg.index_topk)
        bits = None
    return (_attn_out(blk, x, h, o, cfg), IndexStats(loss, kept),
            {"qi": qi, "w": w, "ki": ki, "bits": bits})


def _latent_attention(blk, x, cfg: TransformerConfig, mesh: Optional[Mesh],
                      seq_axis: str, positions=None, mask=CAUSAL):
    """Multi-head latent attention, in the form training runs (keys and
    values re-expanded; nothing attends over the compressed vector):

        cq = RMSNorm(h Wq_a);  q = cq Wq_b             H x [nope ; rope]
        [ckv ; kr] = h Wkv_a;  c = RMSNorm(ckv)
        [k_nope ; v] = c Wkv_b                         H x [nope ; v]
        k = [k_nope ; RoPE(kr)]     one rope key a position, every head's
        o = softmax([q_nope ; RoPE(q_rope)] k^T / sqrt(nope + rope)) v

    one KV head a query head, ``v_head_dim == qk_nope_dim + qk_rope_dim``
    (``__post_init__``), ``W_o`` from ``H x v_head_dim``.  ``positions`` and
    ``mask`` as :func:`_attention` takes them."""
    B, S, _d = x.shape
    H, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.float32)
    h = _rms_norm(x, blk["ln1"], cfg.norm_eps)
    cq = _rms_norm(_mm(h, blk["wq_a"], cfg), blk["q_a_norm"], cfg.norm_eps)
    q = _mm(cq, blk["wq_b"], cfg).reshape(B, S, H, dn + dr)
    ckv, kr = jnp.split(_mm(h, blk["wkv_a"], cfg), [cfg.kv_lora_rank], -1)
    c = _rms_norm(ckv, blk["kv_a_norm"], cfg.norm_eps)
    k_nope, v = jnp.split(_mm(c, blk["wkv_b"], cfg).reshape(B, S, H, dn + dv),
                          [dn], -1)
    q = jnp.concatenate(
        [q[..., :dn], _rope(q[..., dn:], cfg.rope_base, positions)], -1)
    kr = _rope(kr[:, :, None, :], cfg.rope_base, positions)    # (B, S, 1, dr)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr, (B, S, H, dr))], -1)
    o = _attend(q, k, v, cfg, mesh, seq_axis, mask)
    o = o.reshape(B, S, H * dv).astype(x.dtype)
    return x + _post_norm(_mm(o, blk["wo"], cfg), blk, "ln1_post", cfg)


def _short_conv(blk, x, cfg: TransformerConfig):
    """Gated short convolution: ``[B, C, u] = split3(h W_in)``, a causal
    depthwise convolution of ``B * u`` over ``conv_kernel`` taps (tap j
    reads position t - (K-1) + j; zeros left of the sequence), gated by
    ``C`` and projected out."""
    S, K = x.shape[1], cfg.conv_kernel
    h = _rms_norm(x, blk["ln1"], cfg.norm_eps)
    b, c, u = jnp.split(_mm(h, blk["conv_in"], cfg), 3, axis=-1)
    bu = jnp.pad(b * u, ((0, 0), (K - 1, 0), (0, 0)))
    z = sum(blk["conv_w"][j] * bu[:, j:j + S] for j in range(K))
    return x + _post_norm(_mm(c * z, blk["conv_out"], cfg), blk, "ln1_post",
                          cfg)


def _ssm_mixer(blk, x, cfg: TransformerConfig):
    """A Mamba-2 mixer (arXiv 2405.21060, as ``nemotron_h`` lays it out)::

        [z | xBC | dt] = h W_in                  inner | inner + 2 G N | H
        xBC = silu(conv1d_causal(xBC; w, b))     depthwise, ssm_conv taps
        [x | B | C] = xBC;   dt = softplus(dt + dt_bias);   A = -exp(A_log)
        s_t = exp(dt_t A) s_{t-1} + dt_t x_t (x) B_t;   y_t = s_t C_t + D x_t
        out = RMSNorm_groups(y * silu(z); gain) W_out

    ``H`` heads of ``ssm_head_dim`` with a ``ssm_state``-wide state, ``B`` /
    ``C`` shared by the ``H / G`` heads of a group, the norm over each of the
    ``G`` groups of ``inner / G``.  ``dt``, ``A`` and the recurrence's decays
    are f32 (``parallel/ssm.py::chunked_scan``, scope ``ssm_scan``)."""
    B, S, _d = x.shape
    H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    inner, K = cfg.ssm_inner, cfg.ssm_conv
    h = _rms_norm(x, blk["ln1"], cfg.norm_eps)
    z, xbc, dt = jnp.split(_mm(h, blk["ssm_in"], cfg),
                           [inner, 2 * inner + 2 * G * N], axis=-1)
    # tap j reads position t - (K-1) + j; zeros left of the sequence
    xbc = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(blk["ssm_conv_w"][j] * xbc[:, j:j + S]
                          for j in range(K)) + blk["ssm_conv_b"])
    xs, b, c = jnp.split(xbc, [inner, inner + G * N], axis=-1)
    xs = xs.reshape(B, S, H, P)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + blk["dt_bias"])
    with obs.named_scope("ssm_scan"):
        y = chunked_scan(xs, dt, -jnp.exp(blk["A_log"].astype(jnp.float32)),
                         b.reshape(B, S, G, N), c.reshape(B, S, G, N),
                         chunk=cfg.ssm_chunk, compute_dtype=cfg.matmul_dtype)
    y = (y + blk["D"][:, None] * xs).astype(x.dtype).reshape(B, S, inner)
    y = _rms_norm((y * jax.nn.silu(z)).reshape(B, S, G, inner // G), 1.0,
                  cfg.norm_eps).reshape(B, S, inner) * blk["ssm_norm"]
    return x + _post_norm(_mm(y, blk["ssm_out"], cfg), blk, "ln1_post", cfg)


def _no_stats() -> MoEStats:
    return MoEStats(*(jnp.float32(0.0),) * len(MoEStats._fields))


def _swiglu(h, w_gate, w_up, w_down, cfg: TransformerConfig):
    return _mm(jax.nn.silu(_mm(h, w_gate, cfg)) * _mm(h, w_up, cfg), w_down,
               cfg)


def _shared_expert(blk, tokens, cfg: TransformerConfig):
    """The shared experts, in the routed experts' form."""
    if "shared_gate" in blk:
        return _swiglu(tokens, blk["shared_gate"], blk["shared_up"],
                       blk["shared_down"], cfg)
    return _mm(ACTIVATIONS[cfg.expert_act](_mm(tokens, blk["shared_up"],
                                               cfg)), blk["shared_down"], cfg)


def _ffn(blk, x, cfg: TransformerConfig, mesh: Optional[Mesh],
         expert_axis: str, kind: str):
    """-> (x + ffn, aux loss, MoEStats of this layer); ``x`` itself for
    kind ``none``."""
    B, S, d = x.shape
    if kind == "none":
        return x, jnp.float32(0.0), _no_stats()
    if kind == "moe":
        with obs.named_scope("route"):
            tokens = _rms_norm(x, blk["ln2"], cfg.norm_eps).reshape(B * S, d)
        if mesh is not None and expert_axis in mesh.axis_names:
            y, aux = moe_ffn(blk["moe"], tokens, mesh, axis=expert_axis,
                             k=cfg.moe_top_k, router=cfg.router,
                             compute_dtype=cfg.matmul_dtype,
                             route_scale=cfg.route_scale, act=cfg.expert_act)
            stats = _no_stats()
        else:
            y, aux, stats = expert_layer(
                blk["moe"], tokens, k=cfg.moe_top_k, router=cfg.router,
                held=cfg.held, compute_dtype=cfg.matmul_dtype,
                route_scale=cfg.route_scale, act=cfg.expert_act)
        y = y.astype(x.dtype)
        if cfg.n_shared_experts:
            # every token, on every chip that shares the layer: a dense
            # product outside the routed experts' sort and groups
            with obs.named_scope("shared_expert"):
                y = y + _shared_expert(blk, tokens, cfg)
        y = y.reshape(B, S, d)
        with obs.named_scope("route"):
            y = _post_norm(y, blk, "ln2_post", cfg)
        return x + y, aux, stats
    with obs.named_scope("dense_ffn"):
        h = _rms_norm(x, blk["ln2"], cfg.norm_eps)
        y = _post_norm(_swiglu(h, blk["w_gate"], blk["w_up"], blk["w_down"],
                               cfg), blk, "ln2_post", cfg)
    return x + y, jnp.float32(0.0), _no_stats()


def _remat_policy(cfg: TransformerConfig):
    """checkpoint policy for the block body.  "dots": save dot outputs
    that have no batch dims — i.e. the wq/wk/wv/wo and FFN weight
    matmuls — while the (b, h)-batched score/PV einsums (the S x S
    intermediates, the memory remat exists to shed) are recomputed.
    "full": save nothing (the round-5 pre-policy behavior; its measured
    B=256 cell recomputed every matmul)."""
    if cfg.remat_policy == "full":
        return None
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(f"unknown remat_policy: {cfg.remat_policy!r}")


def _operator_stats(blk, x, cfg: TransformerConfig, mesh: Optional[Mesh],
                    seq_axis: str, op: str, **attn):
    """A layer's operator -> (x, a sparse layer's :class:`IndexStats`, else
    ``None``)."""
    if op == "none":
        return x, None
    if op == "ssm":
        if attn.get("mask", CAUSAL) is not CAUSAL:
            raise ValueError("an 'ssm' layer's recurrence is causal; it "
                             "takes no mask")
        with obs.named_scope("ssm_mixer"):
            return _ssm_mixer(blk, x, cfg), None
    if op in ATTENTION_OPS:
        scope, windowed, rotary = ATTENTION_OPS[op]
        if windowed:
            attn = {**attn, "mask": WindowMask(cfg.window)}
        with obs.named_scope(scope):
            if op == "sparse":
                return _sparse_attention(blk, x, cfg, **attn)[:2]
            if op == "latent":     # its rope part is not an option
                return _latent_attention(blk, x, cfg, mesh, seq_axis,
                                         **attn), None
            return _attention(blk, x, cfg, mesh, seq_axis, rotary=rotary,
                              **attn), None
    with obs.named_scope("conv"):
        return _short_conv(blk, x, cfg), None


def _operator(blk, x, cfg: TransformerConfig, mesh: Optional[Mesh],
              seq_axis: str, op: str, **attn):
    return _operator_stats(blk, x, cfg, mesh, seq_axis, op, **attn)[0]


def _layer(blk, x, cfg: TransformerConfig, mesh: Optional[Mesh], kind,
           *, seq_axis: str = "seq", expert_axis: str = "expert", **attn):
    """One layer of ``kind`` -> (x, aux loss, MoEStats, a sparse layer's
    :class:`IndexStats` or ``None``)."""
    op, ffn = kind
    x, index = _operator_stats(blk, x, cfg, mesh, seq_axis, op, **attn)
    return (*_ffn(blk, x, cfg, mesh, expert_axis, ffn), index)


def block_apply(blk, x, cfg: TransformerConfig, mesh: Optional[Mesh] = None,
                *, kind: Optional[Tuple[str, str]] = None, **kwargs):
    """One layer of ``kind`` = (operator, FFN) (default: layer 0's)
    -> (x, aux loss, MoEStats); a sparse layer's index loss is
    :func:`_layer`'s fourth.  ``kwargs``: ``seq_axis``, ``expert_axis`` and
    :func:`_attention`'s ``positions`` and ``mask``."""
    return _layer(blk, x, cfg, mesh, kind or cfg.layer_kinds()[0],
                  **kwargs)[:3]


def _embed(params, tokens, cfg: TransformerConfig):
    x = params["embed"][tokens]
    return x if cfg.embed_scale == 1.0 else x * cfg.embed_scale


def _groups(params, cfg: TransformerConfig):
    """[(kind, layers, stacked parameters)] of the runs of equal layers."""
    stacks = params["blocks"] if cfg.heterogeneous else (params["blocks"],)
    return [(kind, n, stacked) for (kind, n), stacked in
            zip(cfg.layer_groups(), stacks)]


def _run_layers(stacked, carry, cfg: TransformerConfig, mesh, kind,
                **block_kwargs):
    """``carry = (x, aux, MoEStats, IndexStats or None)`` through a run of
    equal layers: one compiled block body whatever the run's length — a scan
    over the stacked params instead of unrolled copies."""
    def body(carry, blk):
        x, aux, stats, index = carry
        x, a, st, ix = _layer(blk, x, cfg, mesh, kind, **block_kwargs)
        if ix is not None:
            index = IndexStats(*(s + t for s, t in zip(index, ix)))
        return (x, aux + a, MoEStats(*(s + t for s, t in zip(stats, st))),
                index), None

    if cfg.remat:
        body = jax.checkpoint(body, policy=_remat_policy(cfg))
    return jax.lax.scan(body, carry, stacked)[0]


def _stack(params, tokens, cfg: TransformerConfig,
           mesh: Optional[Mesh] = None, *, seq_axis: str = "seq",
           expert_axis: str = "expert", **attn):
    """:func:`trunk` up to the last layer's output, *before* the final
    norm: what a multi-token-prediction module reads -> (x, aux, MoEStats,
    the sparse layers' :class:`IndexStats`, ``None`` for a stack without
    them)."""
    with obs.named_scope("embed"):
        x = _embed(params, tokens, cfg)
    carry = (x, jnp.float32(0.0), _no_stats(),
             _no_index_stats() if "sparse" in cfg.layer_ops else None)
    for kind, _n, stacked in _groups(params, cfg):
        carry = _run_layers(stacked, carry, cfg, mesh, kind,
                            seq_axis=seq_axis, expert_axis=expert_axis,
                            **attn)
    return carry


def trunk(params, tokens, cfg: TransformerConfig,
          mesh: Optional[Mesh] = None, **kwargs):
    """tokens (B, S) int32 -> (hidden (B, S, d) after the final norm, aux
    loss, MoEStats summed over the expert layers).  A plain sequence
    under causal attention unless ``kwargs`` (:func:`_attention`'s
    ``positions`` and ``mask``; ``seq_axis``, ``expert_axis``) say
    otherwise: the ``block_diffusion`` loss runs ``[x_t ; x_0]`` (B, 2S)
    with ``diffusion.attention_inputs``."""
    x, aux, stats, _index = _stack(params, tokens, cfg, mesh, **kwargs)
    with obs.named_scope("head"):
        x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x, aux, stats


def _mtp_merge(params, x_last, tokens, cfg: TransformerConfig):
    """The module's input: ``[RMSNorm(x_last) ; RMSNorm(E[next token])]
    W_eh`` at every position (the last one reads the sequence's first
    token again; nothing earlier sees it and its loss is left out)."""
    m = params["mtp"]
    e = _embed(params, jnp.roll(tokens, -1, axis=1), cfg)
    return _mm(jnp.concatenate(
        [_rms_norm(x_last, m["hnorm"], cfg.norm_eps),
         _rms_norm(e, m["enorm"], cfg.norm_eps)], -1), m["eh_proj"], cfg)


def _mtp(params, x_last, tokens, cfg: TransformerConfig,
         mesh: Optional[Mesh] = None, **block_kwargs):
    """The multi-token-prediction module (depth 1): ``x_last`` (B, S, d),
    the trunk's last hidden state before the final norm, merged with the
    next token's embedding, through one block of the last layer's kind over
    positions ``0..S-1`` (causal: the sequence keeps its length, so the
    attention walks the tiles the trunk's layers walk) -> (hidden after the
    module's own gain, aux, the module's MoEStats).  The embedding is the
    trunk's, and so is the head the caller runs over the result."""
    z = _mtp_merge(params, x_last, tokens, cfg)
    z, aux, stats, _index = _run_layers(
        params["mtp"]["block"], (z, jnp.float32(0.0), _no_stats(), None),
        cfg, mesh, cfg.layer_kinds()[-1], **block_kwargs)
    return _rms_norm(z, params["mtp"]["norm"], cfg.norm_eps), aux, stats


def forward(params, tokens, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None, *, seq_axis: str = "seq",
            expert_axis: str = "expert"):
    """tokens (B, S) int32 -> (logits (B, S, V), aux_loss), through the
    head :func:`head_matrix` names."""
    x, aux, _ = trunk(params, tokens, cfg, mesh, seq_axis=seq_axis,
                      expert_axis=expert_axis)
    with obs.named_scope("head"):
        return _mm(x, head_matrix(params, cfg).T, cfg), aux


def hidden_states(params, tokens, cfg: TransformerConfig, **attn):
    """The residual stream as ``trunk`` computes it on one device, at every
    half layer: ``[x_0, m_0, x_1, m_1, ..., x_L]`` with ``x_i`` the input of
    layer ``i``'s operator, ``m_i`` the input of its FFN and ``x_L`` the
    last layer's output (for holding each operator and FFN to a reference
    on the program's own input).  With a multi-token-prediction module,
    three more: the module's merged input, its FFN's input and its block's
    output (before the module's gain).  ``attn`` as :func:`trunk` takes
    it."""
    x = _embed(params, tokens, cfg)
    out = [x]

    def layers(stacked, kind, x):
        op, ffn = kind
        for i in range(jax.tree.leaves(stacked)[0].shape[0]):
            blk = jax.tree.map(lambda a: a[i], stacked)
            mid = _operator(blk, x, cfg, None, "seq", op, **attn)
            x = _ffn(blk, mid, cfg, None, "expert", ffn)[0]
            out.extend([mid, x])
        return x

    for kind, _n, stacked in _groups(params, cfg):
        x = layers(stacked, kind, x)
    if cfg.mtp_layers:
        out.append(_mtp_merge(params, x, tokens, cfg))
        layers(params["mtp"]["block"], cfg.layer_kinds()[-1], out[-1])
    return out


def sparse_probe(blk, x, cfg: TransformerConfig) -> dict:
    """What one sparse layer makes of the input ``x`` (B, S, d) beside its
    output, for holding it to a reference on the program's own input (as
    :func:`hidden_states` gives it): the indexer's ``qi``, ``w``, ``ki`` as
    the index scores' product takes them
    (``parallel/sparse_attention.py::index_tile``), the selection the
    attention used as packed ``bits`` in tiles of ``min(attn_block, S)``
    (``unpack`` there; ``None`` under ``attention="full"``), the layer's
    ``index_loss`` and the pairs ``kept``."""
    _out, stats, probe = _sparse_attention(blk, x, cfg)
    return {**probe, "index_loss": stats.loss, "kept": stats.kept}


def forward_pipelined(params, tokens, cfg: TransformerConfig, mesh: Mesh,
                      *, stage_axis: str = "stage",
                      num_microbatches: int = 4):
    """Same function, trunk run as a stage pipeline over ``stage_axis``
    (one block per stage: n_layers must equal the axis size).  Embed and
    head stay outside the pipelined trunk (homogeneous-activation rule).
    Dense-FFN, local attention — the pipeline composes with dp, not with
    the collective attention variants (one shard_map at a time)."""
    if cfg.objective != "next_token":
        raise ValueError("pipelined trunk runs objective 'next_token' only, "
                         f"not {cfg.objective!r}: its attention is causal")
    if cfg.n_experts or cfg.attention != "full" or cfg.heterogeneous:
        raise ValueError("pipelined trunk requires full attention and "
                         "dense FFN (nested shard_map is not supported)")
    x = _embed(params, tokens, cfg)

    def stage_fn(blk, act):
        return block_apply(blk, act, cfg, None)[0]

    if cfg.remat:
        stage_fn = jax.checkpoint(stage_fn, policy=_remat_policy(cfg))

    x = pipeline_apply(stage_fn, params["blocks"], x, mesh,
                       axis=stage_axis, num_microbatches=num_microbatches)
    x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x @ head_matrix(params, cfg).T, jnp.float32(0.0)


# -- training --------------------------------------------------------------

def _token_nll(x, head, targets, cfg: TransformerConfig):
    """Per-token negative log likelihood of ``targets``, f32: x (N, d)
    hidden, head (V, d) (:func:`head_matrix`), targets (N,) -> (N,).  The
    logits and their log-sum-exp exist for ``loss_chunk`` tokens at a time
    and are recomputed in the backward pass, so (N, V) f32 is never held."""
    def nll(xc, tc):
        logits = _mm(xc, head.T, cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, tc[:, None], -1)[:, 0]

    n, chunk = x.shape[0], cfg.loss_chunk
    if not chunk or n <= chunk:
        return nll(x, targets)
    if n % chunk:
        raise ValueError(f"{n} tokens are no multiple of loss_chunk {chunk}")
    out = jax.lax.map(lambda c: jax.checkpoint(nll)(*c),
                      (x.reshape(n // chunk, chunk, -1),
                       targets.reshape(n // chunk, chunk)))
    return out.reshape(n)


def lm_loss_and_stats(params, tokens, cfg: TransformerConfig,
                      mesh: Optional[Mesh] = None, aux_weight: float = 0.01,
                      noise_key=None, **fwd_kwargs):
    """(``cfg.objective``'s loss + weighted MoE aux, (MoEStats summed over
    the expert layers, the loss's parts)).

    ``next_token``: the whole sequence runs through the trunk (so a packed
    sequence keeps its length); a sequence's last position has no target
    and carries no loss.  With ``mtp_layers`` the module (:func:`_mtp`)
    runs behind the trunk and the same head a second time: ``loss =
    main_loss + mtp_weight * mtp_loss``, the second the cross entropy of
    the token after the next (a sequence's last two positions left out);
    the parts are ``{"main_loss", "mtp_loss", "mtp_stats"}`` — the module's
    own MoEStats, which the sum holds too — and none of them without a
    module.  Where the stack has sparse layers, ``loss = main_loss +
    index_loss`` (the layers' index losses summed, weight 1: the two train
    disjoint parameters) and the parts hold both, with
    ``index_loss_per_layer`` and, counted from the selections the attention
    used, ``selected_keys_per_query`` (mean over queries and layers) and
    ``selected_pair_share`` (percent of the causal pairs kept).  Where the
    stack has state-space layers the parts hold
    ``ssm_scan_chunks``: the chunks their scans walked this step (sequences
    x chunks a sequence x such layers), from the shapes traced here.
    ``block_diffusion``: ``noise_key`` draws the
    step's noise (:func:`diffusion.block_noise`), the trunk runs
    ``[x_t ; x_0]``, and head and loss run over the noised half alone: a
    masked position predicts its own token, weighted 1/t."""
    B, S = tokens.shape
    diffuse = cfg.objective == "block_diffusion"
    inputs, attn = tokens, {}
    if diffuse:
        if noise_key is None:
            raise ValueError("objective 'block_diffusion' needs a noise_key")
        with obs.named_scope("noise"):
            noisy, weights = diffusion.block_noise(noise_key, tokens, cfg)
            inputs = diffusion.trunk_input(noisy, tokens)
            attn = diffusion.attention_inputs(S, cfg)
    x, aux, stats, index = _stack(params, inputs, cfg, mesh, **attn,
                                  **fwd_kwargs)
    parts = {}
    ops = [kind[0] for kind in cfg.layer_kinds()]
    n_ssm = ops.count("ssm") + (cfg.mtp_layers and ops[-1] == "ssm")
    if n_ssm:
        parts["ssm_scan_chunks"] = jnp.float32(
            B * n_chunks(S, cfg.ssm_chunk) * n_ssm)
    if cfg.mtp_layers:
        # everything the module adds books under `mtp`: its layers' own
        # scopes by the prefix, its merge, gain, head pass and loss here
        with obs.named_scope("mtp"), obs.scope_prefix("mtp_"):
            z, aux_m, stats_m = _mtp(params, x, tokens, cfg, mesh,
                                     **fwd_kwargs)
            nll = _token_nll(z.reshape(B * S, -1), head_matrix(params, cfg),
                             jnp.roll(tokens, -2, axis=1).reshape(B * S),
                             cfg).reshape(B, S)
            parts.update(mtp_loss=nll[:, :-2].mean(), mtp_stats=stats_m)
        aux = aux + aux_m
        stats = MoEStats(*(s + t for s, t in zip(stats, stats_m)))
    with obs.named_scope("head"):
        x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    if diffuse:
        x = x[:, :S]
    with obs.named_scope("head"):
        targets = tokens if diffuse else jnp.roll(tokens, -1, axis=1)
        nll = _token_nll(x.reshape(B * S, -1), head_matrix(params, cfg),
                         targets.reshape(B * S), cfg).reshape(B, S)
        loss = diffusion.weighted_loss(nll, weights) if diffuse \
            else nll[:, :-1].mean()
    if cfg.mtp_layers:
        parts["main_loss"] = loss
        loss = loss + cfg.mtp_weight * parts["mtp_loss"]
    if index is not None:
        n, queries = ops.count("sparse"), B * S
        kept = index.kept.astype(jnp.float32)
        parts.update(
            main_loss=loss, index_loss=index.loss,
            index_loss_per_layer=index.loss / n,
            selected_keys_per_query=kept / (n * queries),
            selected_pair_share=100.0 * kept / (n * queries * (S + 1) / 2))
        loss = loss + index.loss
    return loss + aux_weight * aux, (stats, parts)


def lm_loss(params, tokens, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None, aux_weight: float = 0.01,
            **fwd_kwargs):
    """``cfg.objective``'s loss (+ weighted MoE aux)."""
    return lm_loss_and_stats(params, tokens, cfg, mesh, aux_weight,
                             **fwd_kwargs)[0]


@partial(jax.jit, static_argnames=("cfg", "lr"), donate_argnums=0)
def sgd_step(params, tokens, cfg: TransformerConfig, lr: float = 0.1):
    """One SGD training step.  Under a mesh, dp/tp come from the shardings
    of ``params``/``tokens`` (GSPMD inserts the collectives); no
    parallelism code appears here at all — the point of the design."""
    loss, grads = jax.value_and_grad(lm_loss)(params, tokens, cfg)
    new = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                       params, grads)
    return new, loss
