"""The latent-attention language-model family: ``families/lm.py``'s call
sequence — ``TransformerConfig`` -> ``Trainer(cfg, **optimizer).init_state
(key)`` -> ``Trainer.run(state, host batches)`` on packed sequences cut from
the traffic mix's token stream — for a stack whose every attention layer is
*latent* (queries through a low-rank pair, keys and values re-expanded per
head from one compressed vector, RoPE on a decoupled quarter of the head whose
key all heads share), whose expert layers add a shared expert beside routed
ones weighted by a scaled, normalised sigmoid, and behind whose trunk a
multi-token-prediction module runs one more block and the same head a second
time: loss = next-token cross entropy + ``mtp_weight`` x the cross entropy of
the token after the next.

What this family touches in the program beyond what ``families/lm.py``'s and
``families/swlm.py``'s heads list (``tests/test_benchmark_rehearsal.py::
test_harness_surface[mlalm_*]`` pins it): the ``TransformerConfig`` fields
``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_dim``, ``qk_rope_dim``,
``v_head_dim``, ``mtp_layers``, ``mtp_weight`` and the operator kind
``"latent"`` of ``layer_ops``; the parameter names ``wq_a``, ``q_a_norm``,
``wq_b``, ``wkv_a``, ``kv_a_norm``, ``wkv_b`` (``wo``, ``ln1``, ``ln2`` as
every layer) and ``params["mtp"]`` = ``{"hnorm", "enorm", "eh_proj", "block",
"norm"}`` with ``block`` a run of one layer; ``hidden_states``'s three extra
entries for the module (merged input, its FFN's input, its block's output);
``Trainer.train_metrics``'s ``main_loss``, ``mtp_loss``, ``mtp_loss_share``
(telemetry on); the device scopes ``latent_attention`` and ``mtp``.

The all-cell metrics read here as in ``families/lm.py``: a "pair" is a
position with a next token.
"""

from __future__ import annotations

import time

import numpy as np

from ..lib import traffic as traffic_gen
from ..reference import mlalm as reference
from . import lm
from .lm import ROW_SAMPLE, WEIGHTS_KEY, _rel, trainer_kwargs  # noqa: F401

#: the seed of the token stream the trainer is fed (the steps that train, the
#: warm-up and the timed window), whatever ``--seed``, as in
#: ``families/swlm.py`` and ``families/bdlm.py`` and for their reason: **a
#: step's time follows its batch**.  On six seeds' own streams (my chip runs,
#: PR 42, the cell as first built) a run's chunks lay within ~0.4 % of one
#: another but the runs' medians read 16,581.9 16,653.8 16,752.0 16,776.6
#: 16,884.8 16,900.3 tokens/s: a range of 1.9 % and a quartile spread of
#: 1.5 %, against the 0.5 % a new cell may spread in each of two sets of six —
#: the held experts' share of a layer's picks runs from 3 to 27 % by layer on
#: the untrained weights (``configs/glm-4.7-flash-ep8.json`` ``reduced_why``)
#: and moves with the stream's hot tokens, and the expert loop walks whole
#: 8,192-row chunks of a layer's 32,768 picks; ``train_loss_fixed`` read
#: 11.24-11.52 there (the five steps that train saw the seed's batches).  So
#: every run trains on and times the same batches, and a change is compared
#: with its parent on equal steps.  ``--seed`` makes the batch of the
#: half-layer check and the held-out sequence.
STREAM_SEED = 42
#: limits of the first-step comparison.  Each lies between two readings on
#: the chip at the timed sizes (my chip runs, PR 42; PERF.md section 6;
#: ``tools/mlalm_lower_precision.py``): what the program gives with the bf16
#: operands the configuration states against the plain reference, the largest
#: over the seeds' own batches, and what the reference gives with
#: float8-e4m3 operands against itself, which fails the loss limit, all four
#: per-part limits and 37 of the 42 gradient limits.
LIMITS = {
    # |L_program - L_reference| / L_reference of the whole objective
    # L_main + 0.3 L_mtp; precision hardly moves it: readings 3.7e-6-5.7e-5
    # (the reference with bf16-rounded operands 5.4e-6); float8 2.8e-3
    "loss": 3e-4,
    # per token, |update_program - update_reference| over the larger of the
    # token's own reference update norm and the sequence's root-mean-square
    # one (reference/bdlm.py::update_error), the largest over the 8,192
    # positions, of the stack's ten half layers and the module's two.
    # Readings: latent 4.9e-3-5.4e-3 (float8 1.0: the rope part's scores do
    # not survive three mantissa bits), dense 4.4e-3 (float8 5.2e-2), moe
    # 4.7e-3-4.8e-3 (float8 6.6e-2)
    "latent": 2.5e-2, "dense": 1.5e-2, "moe": 2e-2,
    # the module's merged input [norm(h) ; norm(e)] W_eh, per token over its
    # own norm (reference/mlalm.py::merge_error): one 4,096-term
    # contraction.  Readings 2.5e-3 on every seed; float8 2.9e-2
    "merge": 8e-3,
    # per sampled tensor, the Frobenius distance of AdamW's first moment
    # / (1 - b1) from the reference's clipped gradient, over its norm:
    # readings up to 9.9e-3 (the compressed queries' gain) for every tensor
    # but the routers; float8 ~1.0 for every matrix and gain inside a layer
    # (0.002-0.11 for the head's rows, the two final gains and the module's
    # shared down-projection, which see the rounding once)
    "grad": 5e-2,
    # ... of a router (the stack's first and the module's): on a share its
    # gradient is the small part the 8 held experts leave, summed over the
    # tokens that pick them, so the few tokens whose picks part ways between
    # the step and ``hidden_states`` (``fullest_experts`` says how) weigh
    # more in it than rounding does: readings 5.5e-3-2.6e-2 (stack) and
    # 3.5e-2-6.1e-2 (module) over six seeds' batches, where the reference
    # with bf16-rounded operands and its own picks reads 4.4e-3 / 3.6e-3;
    # float8 0.39 / 0.77
    "grad.router": 2e-1,
    # share of tokens an expert layer may leave out as near ties (0.08-0.15 %
    # at the gap of 2e-5: configs/glm-4.7-flash-ep8.json)
    "ties": 1e-2,
}


def transformer_config(config: dict, traffic: dict):
    """The program's ``TransformerConfig`` of a configuration file."""
    import jax.numpy as jnp

    from swiftmpi_tpu.models.transformer import TransformerConfig

    m = reference.dims(config)
    if int(config["num_key_value_heads"]) != m["heads"]:
        raise ValueError("latent attention re-expands one KV head a query "
                         "head")
    train = config["train"]
    operands = config["precision"]["matmul_operands"]
    return TransformerConfig(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]), n_layers=len(m["kinds"]),
        n_heads=m["heads"],
        q_lora_rank=int(config["q_lora_rank"]), kv_lora_rank=m["kv_rank"],
        qk_nope_dim=m["nope"], qk_rope_dim=m["rope"], v_head_dim=m["v_dim"],
        d_ff=int(config["intermediate_size"]),
        d_expert=int(config["moe_intermediate_size"]),
        max_seq=int(traffic["sentence_tokens"]),
        attention=train["attention"], attn_block=int(train["attn_block"]),
        loss_chunk=int(train["loss_chunk"]),
        remat=bool(train["remat"]), remat_policy=train["remat"] or "full",
        n_experts=int(config["published"]["n_routed_experts"]),
        moe_top_k=m["top_k"], experts_held=tuple(config["experts_held"]),
        router="sigmoid_bias", route_scale=m["scale"],
        n_shared_experts=int(config["n_shared_experts"]),
        expert_gated=True,
        layer_ops=tuple(op for op, _ in m["kinds"]),
        layer_ffns=tuple(ffn for _, ffn in m["kinds"]),
        norm_eps=m["eps"], rope_base=m["theta"],
        init_std=float(config["initializer_range"]),
        tied_head=bool(config["tie_word_embeddings"]),
        mtp_layers=m["mtp"], mtp_weight=m["mtp_weight"],
        matmul_dtype=None if operands == "float32"
        else jnp.dtype(operands))


class Family(lm.Family):
    def __init__(self, config: dict, traffic: dict, seed: int, workdir: str,
                 telemetry: bool, annotate):
        self.config, self.traffic = config, traffic
        self.seed, self.workdir = int(seed), workdir
        self.telemetry, self.annotate = telemetry, annotate
        self.seq_len = int(traffic["sentence_tokens"])
        self.seqs = int(traffic["sequences_per_step"])
        self.vocab = int(config["vocab_size"])
        self.dims = reference.dims(config)
        self.tie_gap = float(config["check"]["tie_gap"])
        self.counters = []
        self.cache_dir = None

    # -- inputs -----------------------------------------------------------------
    def make_inputs(self) -> None:
        """The token stream as packed sequences, from ``STREAM_SEED`` (see
        there); from ``--seed``, one held-out sequence of the same law and one
        batch for the half-layer check.  Token id = the key's rank, as in
        ``lm.Family``."""
        ranks, _offsets = traffic_gen.key_stream(self.traffic, self.vocab,
                                                 STREAM_SEED)
        n = len(ranks) // self.seq_len
        self.sequences = ranks[:n * self.seq_len].reshape(n, self.seq_len)
        if n < self.seqs:
            raise ValueError(f"the stream holds {n} sequences, a step "
                             f"needs {self.seqs}")
        rng = np.random.default_rng([self.seed, 0x1F32])
        p = traffic_gen.rank_probabilities(self.traffic["keys"], self.vocab)
        self.held_out = traffic_gen.draw_ranks(
            rng, p, int(self.traffic["eval_tokens"]))[None, :]
        self.check_batch = traffic_gen.draw_ranks(
            rng, p, self.seqs * self.seq_len).reshape(self.seqs, self.seq_len)
        self._next = 0

    # -- the library user's call sequence -------------------------------------
    def build_model(self) -> None:
        import jax

        from swiftmpi_tpu import obs
        from swiftmpi_tpu.models.trainer import Trainer
        from swiftmpi_tpu.utils.xla_env import ensure_compile_cache

        self.cache_dir = ensure_compile_cache()
        if self.telemetry:
            obs.set_enabled(True)
        self.cfg = transformer_config(self.config, self.traffic)
        self.trainer = Trainer(self.cfg, **trainer_kwargs(self.config))
        self.state = self.trainer.init_state(jax.random.key(WEIGHTS_KEY))
        self.fixed = self._fixed()
        self.ref = reference.Reference(self.dims)
        self._trunk = jax.jit(self._program_hidden)

    def _program_hidden(self, params, batch):
        from swiftmpi_tpu.models.transformer import hidden_states

        return hidden_states(params, batch, self.cfg)

    def _hidden(self, batch) -> list:
        """The program's residual stream at every half layer of ``batch``
        and the module's three states, staged on the host as
        ``families/lm.py`` does: 14 x 67 MB."""
        return [np.asarray(h) for h in self._trunk(self.state.params, batch)]

    def _fixed(self) -> list:
        """``lm.Family._fixed`` and the module's selection bias and router."""
        moe = self.state.params["mtp"]["block"]["moe"]
        return super()._fixed() + [np.asarray(moe.bias),
                                   np.asarray(moe.router)]

    def step_shape(self, chips: int) -> dict:
        """What ``costs/mlalm.py`` counts from; ``held_pick_share`` is the
        median the traced chunks counted, else a uniform router's mean."""
        c, (lo, hi) = self.config, self.config["experts_held"]
        experts = int(c["published"]["n_routed_experts"])
        shares = [m["held_pick_share"] for m in self.counters
                  if "held_pick_share" in m]
        return {"tokens": self.seqs * self.seq_len, "seq_len": self.seq_len,
                "kinds": self.dims["kinds"], "mtp": self.dims["mtp"],
                "d_model": int(c["hidden_size"]),
                "heads": self.dims["heads"],
                "q_rank": int(c["q_lora_rank"]),
                "kv_rank": self.dims["kv_rank"], "nope": self.dims["nope"],
                "rope": self.dims["rope"], "v_dim": self.dims["v_dim"],
                "d_ff": int(c["intermediate_size"]),
                "d_expert": int(c["moe_intermediate_size"]),
                "d_shared": int(c["n_shared_experts"])
                * int(c["moe_intermediate_size"]),
                "experts": experts, "experts_held": hi - lo,
                "top_k": self.dims["top_k"], "vocab": self.vocab,
                "attn_block": int(c["train"]["attn_block"]),
                "held_pick_share": float(np.median(shares)) if shares
                else 100.0 * (hi - lo) / experts,
                "parameters": self._parameters(), "chips": chips}

    # -- correctness ----------------------------------------------------------
    def _sampled(self, tree) -> dict:
        """Host copies of one tensor (or sampled rows) of every kind: the
        first latent layer's seven tensors and two gains; the dense FFN; the
        first expert layer's router, shared expert and one held expert
        (``fullest_experts``); the module's merge, three gains, its block's
        attention, router, shared and one held expert; the
        final gain, embedding and head rows the first batch saw and rows it
        did not."""
        def latent(g, tag):
            out = {f"{tag}{name}": g[name][0, :ROW_SAMPLE] for name in
                   ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")}
            out.update({f"{tag}{name}": g[name][0] for name in
                        ("q_a_norm", "kv_a_norm", "ln1", "ln2")})
            return out

        def experts(g, tag):
            e = self.sample_expert[tag]       # the fullest held expert
            return {tag + "router": g["moe"].router[0],
                    tag + "shared_gate": g["shared_gate"][0, :ROW_SAMPLE],
                    tag + "shared_down": g["shared_down"][0, :ROW_SAMPLE],
                    tag + "expert_w1": g["moe"].w_gate[0, e, :ROW_SAMPLE],
                    tag + "expert_w3": g["moe"].w_in[0, e, :ROW_SAMPLE],
                    tag + "expert_w2": g["moe"].w_out[0, e, :ROW_SAMPLE]}

        out = latent(tree["blocks"][0], "")
        for (_op, ffn), g in zip((k for k, _n in self.cfg.layer_groups()),
                                 tree["blocks"]):
            if ffn == "dense" and "w_down" not in out:
                out.update(w_gate=g["w_gate"][0, :ROW_SAMPLE],
                           w_down=g["w_down"][0, :ROW_SAMPLE])
            if ffn == "moe" and "router" not in out:
                out.update(experts(g, ""))
        mtp = tree["mtp"]
        out.update(latent(mtp["block"], "mtp."))
        out.update(experts(mtp["block"], "mtp."))
        out.update({"mtp.eh_proj": mtp["eh_proj"][:ROW_SAMPLE],
                    "mtp.eh_proj_e": mtp["eh_proj"][-ROW_SAMPLE:],
                    "mtp.hnorm": mtp["hnorm"], "mtp.enorm": mtp["enorm"],
                    "mtp.norm": mtp["norm"]})
        out.update(ln_f=tree["ln_f"],
                   embed_seen=tree["embed"][self.rows_seen],
                   embed_unseen=tree["embed"][self.rows_unseen],
                   head_seen=tree["head"][self.rows_seen],
                   head_unseen=tree["head"][self.rows_unseen])
        return {k: np.asarray(v) for k, v in out.items()}

    def fullest_experts(self, params, batch, hs) -> dict:
        """``{"": e, "mtp.": e}``: in the stack's first expert layer and in
        the module's, the held expert most of ``batch``'s picks land on (by
        the reference's router on the program's own inputs ``hs``), whose
        three matrices' gradients are compared.  Not any held expert: one
        that few tokens pick is picked *barely* — its picks sit at the
        top-4 edge, where the step's and ``hidden_states``' bf16 paths
        (equal but for the order of their f32 sums) part ways for a token
        or two a layer — and a flipped pick among a handful is the whole
        reading (my chip runs, PR 42: expert ``seed % 8`` of the module's
        layer read 0, 6.1e-3, 1.8e-2, 3.5e-2 twice and 1.66e-1 over six
        seeds' batches; the stack's 5.7e-3-9.0e-3; the fullest experts on
        the fixed stream's first batch 1.0e-2-1.4e-2 and 8.4e-3-9.2e-3)."""
        halves = self.halves_at(params, hs)
        first = next(h for h in halves if h[0] == "moe")
        at = {"": first, "mtp.": halves[-1]}
        return {tag: int(sum(self.ref.held_picks(blk, x[b])
                             for b in range(batch.shape[0])).argmax())
                for tag, (_part, blk, x, _got) in at.items()}

    def halves_at(self, params, hs) -> list:
        """[(part, its layer's parameters, the program's input to it, the
        program's output)] of the stack's half layers and then the module's
        two, from ``hidden_states``' list ``hs``."""
        stack = self.ref.halves(params)
        n = len(stack)
        return [(part, blk, hs[i], hs[i + 1])
                for i, (part, blk) in enumerate(stack)] + \
            [(part, blk, hs[n + 1 + i], hs[n + 2 + i])
             for i, (part, blk) in enumerate(self.ref.module_halves(params))]

    def _half_layer_check(self, params, batch, hs) -> dict:
        """``lm.Family._half_layer_check`` over the stack's half layers and
        the module's two, each kind's worst over both, and the module's merge
        (``merge``) on the trunk's last state."""
        import jax.numpy as jnp

        worst, ties, n_moe = {}, [], 0
        for part, blk, x, got in self.halves_at(params, hs):
            for b in range(batch.shape[0]):
                err, gap = self.ref.half_error(part, blk, x[b], got[b])
                if part == "moe":
                    keep = gap >= self.tie_gap
                    ties.append(jnp.sum(~keep))
                    n_moe += err.shape[0]
                    err = jnp.where(keep, err, 0.0)
                worst.setdefault(part, []).append(jnp.max(err))
        n = len(self.ref.halves(params))
        worst["merge"] = [
            self.ref.merge_error(params, hs[n][b], batch[b],
                                 hs[n + 1][b]).max()
            for b in range(batch.shape[0])]
        return {"worst": {k: float(jnp.max(jnp.stack(v)))
                          for k, v in worst.items()},
                "tie_share": float(sum(ties)) / max(n_moe, 1)}

    def first_step_check(self) -> dict:
        """Hold the program to the plain reference at the timed sizes.  From
        ``--seed``'s check batch: every half layer of the stack, the
        module's merge and its two half layers, each on the program's own
        input.  From the timed first step itself (the stream's first
        batch): the whole objective it returned against the reference's own
        forward pass, and the gradient it left in AdamW's first moment
        against the reference's backward pass through both heads."""
        params = self.state.params
        hs = self._hidden(self.check_batch)
        layer = self._half_layer_check(params, self.check_batch, hs)
        del hs

        batch = self.sequences[(self._next + np.arange(self.seqs))
                               % len(self.sequences)]
        seen = np.unique(batch)
        unseen = np.setdiff1d(np.arange(self.vocab), seen)
        rng = np.random.default_rng([self.seed, 0xF4EE])
        # rows the loss named as targets, and rows no position read or
        # predicted
        self.rows_seen = rng.choice(seen, min(ROW_SAMPLE, len(seen)), False)
        self.rows_unseen = rng.choice(unseen, min(ROW_SAMPLE, len(unseen)),
                                      False) if len(unseen) else seen[:1]
        hs = self._hidden(batch)
        self.sample_expert = self.fullest_experts(params, batch, hs)
        loss_ref, main_ref, mtp_ref = self.ref.losses(params, batch)
        _loss_at, grads = self.ref.loss_and_grads(params, batch, at=hs)
        del hs
        clip = float(self.config["optimizer"]["grad_clip"])
        scale = min(1.0, clip / max(reference.global_norm(grads), 1e-30))
        want = self._sampled(grads)
        del grads
        self.live_before = self._sampled(params)

        t0 = time.perf_counter()
        _words, loss = self.run_chunk(1)
        train_call_s = time.perf_counter() - t0
        b1 = float(self.config["optimizer"]["b1"])
        mu = self._sampled(self.state.opt_state[1][0].mu)

        fields = {"loss": {"max_err": abs(loss - loss_ref) / abs(loss_ref),
                           "limit": LIMITS["loss"]}}
        for name, err in layer["worst"].items():
            fields[name] = {"max_err": err, "limit": LIMITS[name]}
        fields["ties"] = {"max_err": layer["tie_share"],
                          "limit": LIMITS["ties"]}
        for name, g in want.items():
            fields["grad." + name] = {
                "max_err": _rel(mu[name] / (1.0 - b1), scale * g),
                "limit": LIMITS["grad.router" if name.endswith("router")
                                else "grad"]}
        for f in fields.values():
            f["ok"] = bool(np.isfinite(f["max_err"])
                           and f["max_err"] <= f["limit"])
        print(f"[bench] first step: loss {loss:.6f}, reference "
              f"{loss_ref:.6f} = main {main_ref:.6f} + "
              f"{self.dims['mtp_weight']} x mtp {mtp_ref:.6f}; clip scale "
              f"{scale:.4f}; {100 * layer['tie_share']:.3f}% of "
              f"expert-layer tokens left out as near ties (gap < "
              f"{self.tie_gap}); limits {LIMITS}", flush=True)
        return {"ok": all(f["ok"] for f in fields.values())
                and bool(np.isfinite(loss)), "fields": fields, "loss": loss,
                "rows_checked": int(self.check_batch.size),
                "train_call_s": train_call_s, "sampler_max_abs_err": 0.0}

    def rows_check(self) -> dict:
        """``lm.Family.rows_check`` with the module's router beside the
        stack's among what must not move."""
        same = all(np.array_equal(a, b)
                   for a, b in zip(self._fixed(), self.fixed))
        live = self._sampled(self.state.params)
        moved = {k: bool(np.any(v != self.live_before[k]))
                 for k, v in live.items() if not k.endswith("router")}
        finite = all(np.isfinite(v).all() for v in live.values())
        return {"ok": bool(same and all(moved.values()) and finite),
                "buffers_unchanged": bool(same),
                "tensors_moved": f"{sum(moved.values())}/{len(moved)}",
                "finite": bool(finite)}

    def eval_loss(self):
        """The whole objective ``L_main + mtp_weight L_mtp`` of the held-out
        sequence over the vocabulary slice, by the plain reference on the
        parameters as they stand — the benchmark's own number — with its
        two parts printed beside it; the harness's second value is
        ``L_main``."""
        loss, main, mtp = self.ref.losses(self.state.params, self.held_out)
        print(f"[bench] held-out objective {loss:.6f} = main {main:.6f} + "
              f"{self.dims['mtp_weight']} x mtp {mtp:.6f}", flush=True)
        return loss, main
