"""The state-space hybrid language-model family: ``families/lm.py``'s call
sequence — ``TransformerConfig`` -> ``Trainer(cfg, **optimizer).init_state
(key)`` -> ``Trainer.run(state, host batches)`` on packed sequences cut from
the traffic mix's token stream, loss = next-token cross entropy — for a stack
whose every layer is **half** a layer, a mixer or an FFN alone behind one
norm: Mamba-2 state-space mixers (a chunked scan, ``parallel/ssm.py``), an
attention layer over the whole prefix with no position embedding (32 query
heads on 2 KV heads), and expert layers of ungated squared-ReLU experts,
weighted by a scaled, normalised sigmoid, beside a shared expert of the same
form and twice the width.

What this family touches in the program beyond what ``families/lm.py``'s and
``families/swlm.py``'s heads list (``tests/test_benchmark_rehearsal.py::
test_harness_surface[sslm_*]`` pins it): the ``TransformerConfig`` fields
``ssm_heads``, ``ssm_head_dim``, ``ssm_state``, ``ssm_groups``, ``ssm_conv``,
``ssm_chunk``, ``expert_act``, ``d_shared_expert``; the kinds ``"ssm"`` and
``"none"`` of ``layer_ops`` and ``"none"`` of ``layer_ffns``; the parameter
names ``ssm_in``, ``ssm_out``, ``ssm_conv_w``, ``ssm_conv_b``, ``A_log``,
``dt_bias``, ``D``, ``ssm_norm`` (a layer holds ``ln1`` and its operator's,
or ``ln2``, ``moe``, ``shared_up`` and ``shared_down``: no ``shared_gate``,
no ``moe.w_gate``); ``hidden_states``' ``2 L + 1`` entries with a layer's
absent half repeating its input; ``Trainer.train_metrics``'s
``ssm_scan_chunks`` (telemetry on); the device scopes ``ssm_mixer`` and
``ssm_scan``.

The all-cell metrics read here as in ``families/lm.py``: a "pair" is a
position with a next token.
"""

from __future__ import annotations

import time

import numpy as np

from ..lib import traffic as traffic_gen
from ..reference import sslm as reference
from . import lm
from .lm import ROW_SAMPLE, WEIGHTS_KEY, _rel, trainer_kwargs  # noqa: F401

#: the seed of the token stream the trainer is fed (the steps that train, the
#: warm-up and the timed window), whatever ``--seed``, as in
#: ``families/bdlm.py``, ``swlm.py`` and ``mlalm.py`` and for their reason:
#: **a step's time follows its batch** — the held experts' share of a layer's
#: picks moves with the stream's hot tokens and the expert loop walks whole
#: 8,192-row chunks of a layer's 49,152 picks — so every run trains on and
#: times the same batches, and a change is compared with its parent on equal
#: steps.  ``--seed`` makes the batch of the half-layer check and the
#: held-out sequence.
STREAM_SEED = 46
#: the initial step sizes ``models/transformer.py::_init_ssm`` draws: the
#: configuration's ``time_step_min`` / ``_max`` / ``_floor`` must be these
TIME_STEP = (0.001, 0.1, 0.0001)
#: limits of the first-step comparison.  Each lies between two readings on
#: the chip at the timed sizes (my chip runs, PR 46; PERF.md section 6;
#: ``tools/sslm_lower_precision.py``): what the program gives with the bf16
#: operands and float32 decays, sums and states the configuration states
#: against the plain reference, the largest over twelve seeds' own check
#: batches, and what the reference gives in the nearest precision below
#: against itself: float8-e4m3 operands, which fail the loss limit, all three
#: per-kind limits and 21 of the 25 gradient limits.  The recurrence's decays
#: and state held in bfloat16 (``ssm_decay_state_and_sums`` states float32)
#: do **not** fail: they read ``ssm`` 1.05e-2 and ``grad.A_log`` /
#: ``grad.dt_bias`` 8.9e-3 — under two in a hundred heads start slow enough
#: (``dt |A|`` < 2e-3) for a bf16 decay to round to 1, and the grouped norm
#: dilutes them — which is as near the program's own bf16-operand rounding
#: (5.5e-3) as a second seed is, so no limit can stand between the two with
#: room; ``tests/test_nemotron3.py`` holds the decays' precision instead.
LIMITS = {
    # |loss_program - loss_reference| / loss_reference; precision hardly
    # moves it: reading 2.2e-5 on every seed (the stream's first batch and
    # the weights are fixed; the reference with bf16-rounded operands
    # 1.6e-5); float8 1.9e-4
    "loss": 1e-4,
    # per token, |update_program - update_reference| over the larger of the
    # token's own reference update norm and the sequence's root-mean-square
    # one (reference/bdlm.py::update_error), the largest over the 8,192
    # positions, per kind of half layer.  Readings: ssm 5.0e-3-6.8e-3 (the
    # four mixers, chunked scan against sequential recurrence; float8
    # 5.8e-2), full 4.4e-3-4.9e-3 (float8 0.93: without a position embedding
    # the scores of a long prefix lie close and rounding reorders them), moe
    # 4.0e-3-4.2e-3 (float8 5.1e-2)
    "ssm": 2e-2, "full": 2e-2, "moe": 1.5e-2,
    # per sampled tensor, the Frobenius distance of AdamW's first moment
    # / (1 - b1) from the reference's clipped gradient, over its norm:
    # readings up to 2.0e-2 (embedding rows the batch saw; the router 1.7e-2,
    # a mixer's in-projection 1.6e-2, dt_bias 1.5e-2, A_log 1.3e-2); float8
    # 0.75-3.5 for every matrix, gain and per-head vector inside the stack
    # (0.004-0.03 for the head's rows and the final gain, which see the
    # rounding once)
    "grad": 5e-2,
    # share of tokens an expert layer may leave out as near ties (0.12-0.20 %
    # at the gap of 2e-5: configs/nemotron-3-nano-30b-a3b-ep16.json)
    "ties": 1e-2,
}


def transformer_config(config: dict, traffic: dict):
    """The program's ``TransformerConfig`` of a configuration file."""
    import jax.numpy as jnp

    from swiftmpi_tpu.models.transformer import TransformerConfig

    m = reference.dims(config)
    if (config["mlp_hidden_act"], config["mamba_hidden_act"]) \
            != ("relu2", "silu"):
        raise ValueError("this family's experts are squared-ReLU and its "
                         "mixer's activations SiLU")
    steps = tuple(float(config[k]) for k in
                  ("time_step_min", "time_step_max", "time_step_floor"))
    if steps != TIME_STEP:
        raise ValueError(f"the program draws its initial step sizes from "
                         f"{TIME_STEP}, not {steps}")
    if any(config[k] for k in ("attention_bias", "mamba_proj_bias",
                               "mlp_bias", "use_bias")) \
            or not config["use_conv_bias"]:
        raise ValueError("only the convolution has a bias here")
    train = config["train"]
    operands = config["precision"]["matmul_operands"]
    return TransformerConfig(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]), n_layers=len(m["kinds"]),
        n_heads=m["heads"], n_kv_heads=m["kv_heads"],
        d_head=int(config["head_dim"]),
        d_expert=int(config["moe_intermediate_size"]),
        max_seq=int(traffic["sentence_tokens"]),
        attention=train["attention"], attn_block=int(train["attn_block"]),
        loss_chunk=int(train["loss_chunk"]),
        remat=bool(train["remat"]), remat_policy=train["remat"] or "full",
        n_experts=int(config["published"]["n_routed_experts"]),
        moe_top_k=m["top_k"], experts_held=tuple(config["experts_held"]),
        router="sigmoid_bias", route_scale=m["scale"],
        n_shared_experts=int(config["n_shared_experts"]),
        d_shared_expert=int(config["moe_shared_expert_intermediate_size"]),
        expert_gated=False, expert_act="relu2",
        layer_ops=tuple(op for op, _ in m["kinds"]),
        layer_ffns=tuple(ffn for _, ffn in m["kinds"]),
        ssm_heads=m["ssm_heads"], ssm_head_dim=m["ssm_head_dim"],
        ssm_state=m["ssm_state"], ssm_groups=m["ssm_groups"],
        ssm_conv=m["kernel"], ssm_chunk=int(train["ssm_chunk"]),
        norm_eps=m["eps"], init_std=float(config["initializer_range"]),
        tied_head=bool(config["tie_word_embeddings"]),
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
        """The program's residual stream at every half layer of ``batch``,
        staged on the host as ``families/lm.py`` does: 19 x 88 MB."""
        return [np.asarray(h) for h in self._trunk(self.state.params, batch)]

    def step_shape(self, chips: int) -> dict:
        """What ``costs/sslm.py`` counts from; ``held_pick_share`` and
        ``scan_chunks`` are the medians the traced chunks counted, else a
        uniform router's mean and the chunks the shapes give."""
        c, (lo, hi) = self.config, self.config["experts_held"]
        experts = int(c["published"]["n_routed_experts"])
        chunk = int(c["train"]["ssm_chunk"])

        def counted(key, default):
            values = [m[key] for m in self.counters if key in m]
            return float(np.median(values)) if values else default

        n_ssm = sum(op == "ssm" for op, _ in self.dims["kinds"])
        return {"tokens": self.seqs * self.seq_len, "seq_len": self.seq_len,
                "kinds": self.dims["kinds"], "d_model": int(c["hidden_size"]),
                "heads": self.dims["heads"], "kv_heads": self.dims["kv_heads"],
                "d_head": int(c["head_dim"]),
                "ssm_heads": self.dims["ssm_heads"],
                "ssm_head_dim": self.dims["ssm_head_dim"],
                "ssm_state": self.dims["ssm_state"],
                "ssm_groups": self.dims["ssm_groups"],
                "kernel": self.dims["kernel"], "ssm_chunk": chunk,
                "scan_chunks": counted(
                    "ssm_scan_chunks",
                    float(self.seqs * -(-self.seq_len // chunk) * n_ssm)),
                "d_expert": int(c["moe_intermediate_size"]),
                "d_shared": int(c["moe_shared_expert_intermediate_size"]),
                "experts": experts, "experts_held": hi - lo,
                "top_k": self.dims["top_k"], "vocab": self.vocab,
                "attn_block": int(c["train"]["attn_block"]),
                "held_pick_share": counted("held_pick_share",
                                           100.0 * (hi - lo) / experts),
                "parameters": self._parameters(), "chips": chips}

    # -- correctness ----------------------------------------------------------
    def _sampled(self, tree) -> dict:
        """Host copies of one tensor (or sampled rows) of every kind: the
        first state-space layer's two projections, convolution taps and
        bias, ``A_log``, ``dt_bias``, ``D`` and both gains; the attention
        layer's four projections and gain; the first expert layer's router,
        shared expert, gain and its fullest held expert; the final gain,
        embedding and head rows the first batch saw and rows it did not."""
        out = {}
        for (op, ffn), g in zip((k for k, _n in self.cfg.layer_groups()),
                                tree["blocks"]):
            if op == "ssm" and "ssm_in" not in out:
                out.update({name: g[name][0, :ROW_SAMPLE]
                            for name in ("ssm_in", "ssm_out")})
                out.update({name: g[name][0] for name in
                            ("ssm_conv_w", "ssm_conv_b", "A_log", "dt_bias",
                             "D", "ssm_norm")})
                out["ssm.ln1"] = g["ln1"][0]
            if op == "full" and "wq" not in out:
                out.update({name: g[name][0, :ROW_SAMPLE]
                            for name in ("wq", "wk", "wv", "wo")})
                out["full.ln1"] = g["ln1"][0]
            if ffn == "moe" and "router" not in out:
                e = self.sample_expert       # the fullest held expert
                out.update(router=g["moe"].router[0], ln2=g["ln2"][0],
                           shared_up=g["shared_up"][0, :ROW_SAMPLE],
                           shared_down=g["shared_down"][0, :ROW_SAMPLE],
                           expert_up=g["moe"].w_in[0, e, :ROW_SAMPLE],
                           expert_down=g["moe"].w_out[0, e, :ROW_SAMPLE])
        out.update(ln_f=tree["ln_f"],
                   embed_seen=tree["embed"][self.rows_seen],
                   embed_unseen=tree["embed"][self.rows_unseen],
                   head_seen=tree["head"][self.rows_seen],
                   head_unseen=tree["head"][self.rows_unseen])
        return {k: np.asarray(v) for k, v in out.items()}

    def halves_at(self, params, hs) -> list:
        """[(part, its layer's parameters, the program's input to it, the
        program's output)] of the nine half layers the stack has (a layer's
        absent half left out), from ``hidden_states``' list ``hs``."""
        return [(part, blk, hs[i], hs[i + 1])
                for i, (part, blk) in enumerate(self.ref.halves(params))
                if part != "none"]

    def fullest_expert(self, params, batch, hs) -> int:
        """In the stack's first expert layer, the held expert most of
        ``batch``'s picks land on (by the reference's router on the
        program's own input), whose two matrices' gradients are compared:
        ``families/mlalm.py::fullest_experts`` says why not any."""
        _part, blk, x, _got = next(h for h in self.halves_at(params, hs)
                                   if h[0] == "moe")
        return int(sum(self.ref.held_picks(blk, x[b])
                       for b in range(batch.shape[0])).argmax())

    def _half_layer_check(self, params, batch, hs) -> dict:
        """``lm.Family._half_layer_check`` over the half layers the stack
        has, each kind's worst."""
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
        return {"worst": {k: float(jnp.max(jnp.stack(v)))
                          for k, v in worst.items()},
                "tie_share": float(sum(ties)) / max(n_moe, 1)}

    def first_step_check(self) -> dict:
        """Hold the program to the plain reference at the timed sizes.  From
        ``--seed``'s check batch: every half layer on the program's own
        input.  From the timed first step itself (the stream's first batch):
        its loss against the reference's own forward pass and the gradient it
        left in AdamW's first moment against the reference's backward
        pass."""
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
        self.sample_expert = self.fullest_expert(params, batch, hs)
        loss_ref = self.ref.loss(params, batch)
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
                "limit": LIMITS["grad"]}
        for f in fields.values():
            f["ok"] = bool(np.isfinite(f["max_err"])
                           and f["max_err"] <= f["limit"])
        print(f"[bench] first step: loss {loss:.6f}, reference "
              f"{loss_ref:.6f}; clip scale {scale:.4f}; expert "
              f"{self.sample_expert} of the held sampled; "
              f"{100 * layer['tie_share']:.3f}% of expert-layer tokens left "
              f"out as near ties (gap < {self.tie_gap}); limits {LIMITS}",
              flush=True)
        return {"ok": all(f["ok"] for f in fields.values())
                and bool(np.isfinite(loss)), "fields": fields, "loss": loss,
                "rows_checked": int(self.check_batch.size),
                "train_call_s": train_call_s, "sampler_max_abs_err": 0.0}
