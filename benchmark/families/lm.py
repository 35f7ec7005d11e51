"""The language-model family: a block stack built from a configuration's
published keys and trained as a user of the library trains it —
``TransformerConfig`` -> ``Trainer(cfg, **optimizer).init_state(key)``
-> ``Trainer.run(state, host batches)`` — on packed sequences cut from the
traffic mix's token stream.

What the harness touches in the program (``tests/test_benchmark_rehearsal.py
::test_harness_surface`` pins it): ``models.transformer.TransformerConfig``
(the fields :func:`transformer_config` sets) and ``hidden_states``;
``models.trainer.Trainer(cfg, learning_rate=, warmup_steps=, decay_steps=,
weight_decay=, grad_clip=, b1=, b2=)`` with ``.init_state(key)``,
``.run(state, batches)`` (returns ``(state, losses)``; emits the spans
``train_setup input_wait h2d dispatch loss_fetch train_finish``) and
``.train_metrics`` (``stall_ms_per_step``; with telemetry on
``held_pick_share``, ``expert_load_max_over_mean``,
``dropped_picks_per_step``); ``TrainState.params`` / ``.opt_state`` (optax's
``chain(clip, adamw)``: ``opt_state[1][0].mu``); ``obs.set_enabled``;
``obs.costs.phase_map("trainer_step")``;
``utils.xla_env.ensure_compile_cache``.

The per-layer metrics with no list of cells read here too, in the harness's
words: a "pair" is a position with a next token to predict (``S - 1`` a
packed sequence, ``pair_fill_share`` their share of the token grid), counted
from the host batches as the word2vec counters are.
"""

from __future__ import annotations

import time

import numpy as np

from ..lib import traffic as traffic_gen
from ..reference import lm as reference

ROW_SAMPLE = 256
#: the key of ``Trainer.init_state``: the same weights for every ``--seed``
#: (as the word2vec cells' table rows are), with token id = frequency rank.
#: Under Zipf 1.0 ten ids are 30 % of the stream, so *which* experts a seed's
#: weights send them to moves the share of picks that land on the 8 held
#: experts by +-15 % from seed to seed, and with it the step's time by
#: several per cent (PERF.md section 6, PR 31): weights drawn from the seed
#: would make the cell measure the draw.  The seed makes the token stream,
#: its order and the held-out sequence.
WEIGHTS_KEY = 31
#: limits of the first-step comparison.  Each lies between two readings on
#: the chip at the timed sizes (PERF.md section 6, PR 31;
#: ``tools/lm_lower_precision.py``): what the program gives with the bf16
#: operands the configuration states (a product term carries ~2^-9 relative
#: rounding, and a sum of 2,048 random-sign terms keeps about that relative
#: error), at about three times the largest reading over the seeds run, and
#: what float8-e4m3 operands give (2^-4 a term), which fails every one of
#: the per-kind and gradient limits.
LIMITS = {
    # |loss_program - loss_reference| / loss_reference; precision hardly
    # moves it (largest reading 2.4e-5)
    "loss": 1e-4,
    # per token, |update_program - update_reference| over the sequence's
    # root-mean-square update norm, of an operator's or an FFN's residual
    # update given the program's own input: the largest over the tokens
    # compared (reference.update_error).  Readings: conv 7.8e-3, dense
    # 4.7e-3, attention 3.5e-2 (bf16 q and k of norm 8 move a score by
    # ~0.02 and a softmax weight by that share), moe 9.3e-3
    "conv": 2.5e-2, "dense": 1.5e-2, "attention": 1e-1, "moe": 3e-2,
    # per sampled tensor, the Frobenius distance of AdamW's first moment
    # / (1 - b1) from the reference's clipped gradient, over the latter's
    # norm (largest reading 1.6e-2, an expert's matrices)
    "grad": 5e-2,
    # share of tokens an expert layer may leave out as near ties
    "ties": 1e-2,
}


def transformer_config(config: dict, traffic: dict):
    """The program's ``TransformerConfig`` of a configuration file."""
    import jax.numpy as jnp

    from swiftmpi_tpu.models.transformer import TransformerConfig

    m = reference.dims(config)
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    if config.get("head_dim") and int(config["head_dim"]) != d // heads:
        raise ValueError("head_dim must equal hidden_size / heads here")
    train = config["train"]
    operands = config["precision"]["matmul_operands"]
    return TransformerConfig(
        vocab_size=int(config["vocab_size"]), d_model=d,
        n_layers=len(m["kinds"]), n_heads=heads,
        n_kv_heads=int(config["num_key_value_heads"]),
        d_ff=int(config["intermediate_size"]),
        d_expert=int(config["moe_intermediate_size"]),
        max_seq=int(traffic["sentence_tokens"]),
        attention=train["attention"], attn_block=int(train["attn_block"]),
        loss_chunk=int(train["loss_chunk"]),
        remat=bool(train["remat"]), remat_policy=train["remat"] or "full",
        n_experts=int(config["published"]["num_experts"]),
        moe_top_k=int(config["num_experts_per_tok"]),
        experts_held=tuple(config["experts_held"]),
        router="sigmoid_bias" if config["use_expert_bias"] else "softmax",
        expert_gated=True, qk_norm=True,
        layer_ops=tuple(op for op, _ in m["kinds"]),
        layer_ffns=tuple(ffn for _, ffn in m["kinds"]),
        conv_kernel=int(config["conv_L_cache"]),
        norm_eps=float(config["norm_eps"]),
        rope_base=float(config["rope_theta"]),
        init_std=float(config["initializer_range"]),
        matmul_dtype=None if operands == "float32"
        else jnp.dtype(operands))


def trainer_kwargs(config: dict) -> dict:
    o = config["optimizer"]
    return {"optimizer": o["name"], "aux_weight": 0.0,
            **{k: o[k] for k in ("learning_rate", "warmup_steps",
                                 "decay_steps", "weight_decay", "grad_clip",
                                 "b1", "b2")}}


def _rel(a, b) -> float:
    """Frobenius distance of ``a`` from ``b`` over ``b``'s norm."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


class Family:
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
        self.counters = []        # train_metrics of every chunk run
        self.cache_dir = None

    # -- inputs, from the seed ------------------------------------------------
    def make_inputs(self) -> None:
        """The token stream as packed sequences, and one held-out sequence
        drawn from the same law.  Token id = the key's rank (id 0 is the
        most frequent), whatever the seed: see ``WEIGHTS_KEY``."""
        ranks, _offsets = traffic_gen.key_stream(self.traffic, self.vocab,
                                                 self.seed)
        rng = np.random.default_rng([self.seed, 0x1F32])
        n = len(ranks) // self.seq_len
        self.sequences = ranks[:n * self.seq_len].reshape(n, self.seq_len)
        if n < self.seqs:
            raise ValueError(f"the stream holds {n} sequences, a step "
                             f"needs {self.seqs}")
        p = traffic_gen.rank_probabilities(self.traffic["keys"], self.vocab)
        self.held_out = traffic_gen.draw_ranks(
            rng, p, int(self.traffic["eval_tokens"]))[None, :]
        self._next = 0

    def next_batch(self) -> np.ndarray:
        """The next ``seqs`` sequences of the cycled stream, (seqs, S)."""
        with self.annotate("bench/next_batch"):
            idx = (self._next + np.arange(self.seqs)) % len(self.sequences)
            self._next = int(idx[-1] + 1) % len(self.sequences)
            return self.sequences[idx]

    # -- the library user's call sequence -----------------------------------------
    def build_model(self) -> None:
        import jax

        from swiftmpi_tpu import obs
        from swiftmpi_tpu.models.trainer import Trainer
        from swiftmpi_tpu.utils.xla_env import ensure_compile_cache

        self.cache_dir = ensure_compile_cache()
        if self.telemetry:
            obs.set_enabled(True)     # spans, counters, phase_map signatures
        self.cfg = transformer_config(self.config, self.traffic)
        self.trainer = Trainer(self.cfg, **trainer_kwargs(self.config))
        self.state = self.trainer.init_state(jax.random.key(WEIGHTS_KEY))
        self.fixed = self._fixed()
        self.ref = reference.Reference(self.dims)

    def _fixed(self) -> list:
        """What no step may move: the selection biases, and the routers of
        a chip's share (``transformer.is_frozen`` says why)."""
        return [np.asarray(a) for g in self.state.params["blocks"]
                if "moe" in g for a in (g["moe"].bias, g["moe"].router)]

    def step_shape(self, chips: int) -> dict:
        """What ``costs/lm.py`` counts from; ``held_pick_share`` is the
        median the traced chunks counted, else the mean a uniform router
        would give."""
        c, lo_hi = self.config, self.config["experts_held"]
        shares = [m["held_pick_share"] for m in self.counters
                  if "held_pick_share" in m]
        return {"tokens": self.seqs * self.seq_len, "seq_len": self.seq_len,
                "kinds": self.dims["kinds"], "d_model": int(c["hidden_size"]),
                "heads": int(c["num_attention_heads"]),
                "kv_heads": int(c["num_key_value_heads"]),
                "d_ff": int(c["intermediate_size"]),
                "d_expert": int(c["moe_intermediate_size"]),
                "experts": int(c["published"]["num_experts"]),
                "experts_held": lo_hi[1] - lo_hi[0],
                "top_k": int(c["num_experts_per_tok"]),
                "vocab": self.vocab, "kernel": int(c["conv_L_cache"]),
                "held_pick_share": float(np.median(shares)) if shares
                else 100.0 * (lo_hi[1] - lo_hi[0])
                / int(c["published"]["num_experts"]),
                "parameters": self._parameters(), "chips": chips}

    def _parameters(self) -> int:
        import jax

        return int(sum(a.size for a in jax.tree.leaves(self.state.params)))

    def run_chunk(self, steps: int):
        """One ``Trainer.run`` over the next ``steps`` batches, fenced on
        the parameters.  Returns (tokens trained, the last step's loss)."""
        import jax

        pairs = grid = 0

        def batches():
            nonlocal pairs, grid
            for _ in range(steps):
                batch = self.next_batch()
                pairs += batch.shape[0] * (batch.shape[1] - 1)
                grid += batch.size
                yield batch

        with self.annotate("bench/train_call"):
            self.state, losses = self.trainer.run(self.state, batches())
        with self.annotate("bench/fence"):
            jax.block_until_ready(self.state.params)
        self.counters.append({
            "pairs_per_step": pairs / steps,
            "pair_fill_share": 100.0 * pairs / grid,
            **{k: v for k, v in self.trainer.train_metrics.items()
               if isinstance(v, (int, float))}})
        return steps * self.seqs * self.seq_len, float(losses[-1])

    def close(self) -> None:
        pass

    def placement(self, platform: str) -> dict:
        """Where the resident state is: parameters and AdamW's two moments
        (``table_bytes``: the key ``run.py`` prints), every leaf on one
        ``platform`` device."""
        import jax

        out = {"ok": True, "table_bytes": 0, "shards": 1, "why": []}
        for a in jax.tree.leaves((self.state.params, self.state.opt_state)):
            plats = {d.platform for d in a.sharding.device_set}
            if plats != {platform} or len(a.sharding.device_set) != 1:
                out["why"].append(f"a {a.shape} leaf lives on "
                                  f"{sorted(plats)}")
            out["table_bytes"] += a.nbytes
        out["table_bytes_per_device"] = out["table_bytes"]
        out["ok"] = not out["why"]
        return out

    # -- correctness ---------------------------------------------------------------
    def _sampled(self, tree) -> dict:
        """Host copies of one tensor (or sampled rows) of every kind:
        router, one held expert's three matrices, a conv kernel and its
        projections, QK-norm gains, attention and dense-FFN matrices,
        embedding rows the first batch saw and rows it did not."""
        groups = tree["blocks"]
        out = {}
        for (op, ffn), g in zip((k for k, _n in self.cfg.layer_groups()),
                                groups):
            if op == "conv" and "conv_w" not in out:
                out.update(conv_w=g["conv_w"][0],
                           conv_in=g["conv_in"][0, :ROW_SAMPLE],
                           conv_out=g["conv_out"][0, :ROW_SAMPLE])
            if op == "attention" and "q_norm" not in out:
                out.update(q_norm=g["q_norm"][0], k_norm=g["k_norm"][0],
                           wq=g["wq"][0, :ROW_SAMPLE],
                           wk=g["wk"][0, :ROW_SAMPLE])
            if ffn == "dense" and "w_down" not in out:
                out.update(w_down=g["w_down"][0, :ROW_SAMPLE])
            if ffn == "moe" and "router" not in out:
                e = self.seed % g["moe"].w_in.shape[1]    # a held expert
                out.update(router=g["moe"].router[0],
                           expert_w1=g["moe"].w_gate[0, e, :ROW_SAMPLE],
                           expert_w3=g["moe"].w_in[0, e, :ROW_SAMPLE],
                           expert_w2=g["moe"].w_out[0, e, :ROW_SAMPLE])
        out["embed_seen"] = tree["embed"][self.rows_seen]
        out["embed_unseen"] = tree["embed"][self.rows_unseen]
        return {k: np.asarray(v) for k, v in out.items()}

    def _half_layer_check(self, params, batch, hs) -> dict:
        """Every operator and every FFN against the reference on the
        program's own input to it (``hs``): per kind, the largest
        per-token error of the residual update (``reference.update_error``),
        near-tie tokens of an expert layer left out."""
        import jax.numpy as jnp

        worst, ties, n_moe = {}, [], 0
        for i, (part, blk) in enumerate(self.ref.halves(params)):
            for b in range(batch.shape[0]):
                err, gap = self.ref.half_error(part, blk, hs[i][b],
                                               hs[i + 1][b])
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
        """Hold the first train step to the plain reference at the timed
        sizes: every operator and FFN on the program's own input, then —
        from the timed step itself — the loss it returned against the
        reference's own forward pass, and the gradient it left in AdamW's
        first moment against the reference's backward pass."""
        import jax

        from swiftmpi_tpu.models.transformer import hidden_states

        batch = self.sequences[(self._next + np.arange(self.seqs))
                               % len(self.sequences)]
        seen = np.unique(batch)
        unseen = np.setdiff1d(np.arange(self.vocab), seen)
        rng = np.random.default_rng([self.seed, 0xF4EE])
        self.rows_seen = rng.choice(seen, min(ROW_SAMPLE, len(seen)), False)
        self.rows_unseen = rng.choice(unseen, min(ROW_SAMPLE, len(unseen)),
                                      False) if len(unseen) else seen[:1]
        params = self.state.params
        # staged on the host: 11 x 268 MB beside 6.1 GB of resident state,
        # 2 GB of reference gradients and a vjp's working set do not fit
        hs = [np.asarray(h) for h in jax.jit(
            lambda p, t: hidden_states(p, t, self.cfg))(params, batch)]
        layer = self._half_layer_check(params, batch, hs)
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
              f"{loss_ref:.6f}; clip scale {scale:.4f}; "
              f"{100 * layer['tie_share']:.3f}% of expert-layer tokens left "
              f"out as near ties (gap < {self.tie_gap}); limits {LIMITS}",
              flush=True)
        return {"ok": all(f["ok"] for f in fields.values())
                and bool(np.isfinite(loss)), "fields": fields, "loss": loss,
                "rows_checked": int(batch.size),
                "train_call_s": train_call_s, "sampler_max_abs_err": 0.0}

    def rows_check(self) -> dict:
        """The selection biases and the share's routers bit-identical to
        what the key made, sampled tensors of every other kind moved and
        finite."""
        same = all(np.array_equal(a, b)
                   for a, b in zip(self._fixed(), self.fixed))
        live = self._sampled(self.state.params)
        moved = {k: bool(np.any(v != self.live_before[k]))
                 for k, v in live.items() if k != "router"}
        finite = all(np.isfinite(v).all() for v in live.values())
        return {"ok": bool(same and all(moved.values()) and finite),
                "buffers_unchanged": bool(same),
                "tensors_moved": f"{sum(moved.values())}/{len(moved)}",
                "finite": bool(finite)}

    def eval_loss(self):
        """Next-token cross entropy of the held-out sequence over the
        vocabulary slice, by the plain reference on the parameters as they
        stand — the benchmark's own number.  Twice: the harness unpacks
        two values and an LM has no second objective."""
        loss = self.ref.loss(self.state.params, self.held_out)
        return loss, loss
