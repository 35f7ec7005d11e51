"""The block-diffusion language-model family: ``families/lm.py``'s call
sequence — ``TransformerConfig`` -> ``Trainer(cfg, **optimizer)
.init_state(key)`` -> ``Trainer.run(state, host batches)`` on packed
sequences cut from the traffic mix's token stream — for a stack that is
trained by block diffusion (``objective="block_diffusion"``): every sequence
goes through the trunk twice, a noised copy before the clean one, under the
block-diffusion attention mask, and the loss is the 1/t-weighted cross
entropy of the masked positions.

What this family touches in the program beyond what ``families/lm.py``'s
head lists (``tests/test_benchmark_rehearsal.py::test_harness_surface
[bdlm_*]`` pins it): the ``TransformerConfig`` fields ``d_head``,
``tied_head``, ``objective``, ``diffusion_block``, ``mask_token``,
``noise_eps``; ``Trainer.noise_key(step)`` and
``models.diffusion.block_noise(key, tokens, cfg) -> (noisy, weights)``,
``.trunk_input(noisy, tokens)`` and ``.attention_inputs(S, cfg)`` — the step's
own noise drawn again and the trunk's input laid out, as input generation,
not the arithmetic under test; ``hidden_states(params, z, cfg, positions=,
mask=)`` on ``z = [noisy ; tokens]``; ``params["head"]`` (the untied head);
the device scope ``noise``.

The all-cell metrics read here as in ``families/lm.py``: a "pair" is a masked
position, ``pair_fill_share`` their share of the ``B x S`` token grid (about
half: the mean of ``t``), counted in a traced run from the noise drawn
again.  A word is a token trained (``B S`` a step, not a trunk position).
"""

from __future__ import annotations

import time

import numpy as np

from ..lib import traffic as traffic_gen
from ..reference import bdlm as reference
from . import lm
from .lm import ROW_SAMPLE, WEIGHTS_KEY, _rel, trainer_kwargs

#: the seed of the token stream the trainer is fed (the steps that train, the
#: warm-up and the timed window), whatever ``--seed``: as with ``WEIGHTS_KEY``,
#: so that the cell measures the program and not a draw.  ``--seed`` makes
#: the batch and the noise of the half-layer check, and the held-out sequences
#: and their noise.  What forced it (my chip runs, PR 33; PERF.md section 6):
#: (1) the steps that train are Adam's first from a random start: every weight
#: moves by about the rate whatever its gradient's size, and the routing of the
#: window's state follows the batches they saw.  With the seed's own batches
#: there, six seeds' windows ran at 962-1,055 ms a step (``words_per_s``
#: 15,524-16,860, spread 3.4 %) and ``train_loss_fixed`` read 8.79-9.15.
#: (2) The window: at the state it runs on, a step's time follows its batch.
#: The quarter of all positions that carry the one <MASK> row, and each
#: frequent token's rows, pick their experts together, so the held experts'
#: picks swing by whole 8,192-row chunks of the expert loop (~10.5 ms each):
#: 120 steps over six seeds' own batches read 964-1,066 ms, a standard
#: deviation of 15 ms (1.5 %), and the mean of a window's 20 steps 0.35 %
#: over 12 seeds (two sets of six: spread 0.30 % and 0.81 %; the median of
#: one-step chunks 1.03 %).  A new cell may spread 0.5 % in each of two sets
#: of six: at 0.35 % one draw in five passes.  Averaging it down takes 80
#: steps (0.17 %): an 81 s window and as much warm-up in every run of every
#: later check, and 80 traced steps (``chunk_steps`` is the one knob for all
#: three), against the 20 s that ``run_seconds`` gives.  So every run times
#: the same 20 batches (mean 1,019.6 ms, the seeds' batches 1,014), and a
#: change is compared with its parent on equal steps; what a step's time
#: depends on is PERF.md section 5's, for the `benchmark` issue that can
#: give this cell a longer window (section 7).
STREAM_SEED = 33
#: limits of the first-step comparison.  Each lies between two readings on
#: the chip at the timed sizes (my chip runs, PR 33; PERF.md section 6;
#: ``tools/bdlm_lower_precision.py``): what the program gives with the bf16
#: operands the configuration states against the plain reference — the
#: half-layer fields over seven seeds' own batches, the first step's fields
#: one reading (its batch is the stream's first whatever the seed) — and what
#: the reference gives with float8-e4m3 operands against itself, which fails
#: the loss limit, both per-kind limits and 10 of the 14 gradient limits.
LIMITS = {
    # |loss_program - loss_reference| / loss_reference; precision hardly
    # moves it: reading 3.2e-5 (the reference with bf16-rounded operands
    # 3.8e-5), limit about three times that; float8 5.4e-3
    "loss": 1e-4,
    # per token, |update_program - update_reference| over the larger of the
    # token's own reference update norm and the sequence's root-mean-square
    # one (reference.update_error says why not the latter alone: it read one
    # token of one seed's batch at 1.0e-1), the largest over the 2S
    # positions of every sequence.  Readings: attention 5.6e-3-5.9e-3
    # (float8 0.99), moe 5.1e-3-5.5e-3 (float8 9.8e-2)
    "attention": 5e-2, "moe": 2.5e-2,
    # per sampled tensor, the Frobenius distance of AdamW's first moment
    # / (1 - b1) from the reference's clipped gradient, over its norm:
    # largest reading 6.4e-3 (wk); float8 ~1.0 for every matrix inside the
    # stack (0.006-0.035 for the head's rows and the final gain, which see
    # the rounding once)
    "grad": 5e-2,
    # share of tokens an expert layer may leave out as near ties (5.9e-4-
    # 9.7e-4 over seven seeds)
    "ties": 1e-2,
    # share of the first batch's positions at which the reference's own
    # noise function and the program's block_noise disagree, from one key
    # (0: a wrong law disagrees at about half of them)
    "noise": 1e-4,
}


def transformer_config(config: dict, traffic: dict):
    """The program's ``TransformerConfig`` of a configuration file."""
    import jax.numpy as jnp

    from swiftmpi_tpu.models.transformer import TransformerConfig

    train, d = config["train"], config["diffusion"]
    operands = config["precision"]["matmul_operands"]
    layers = int(config["num_hidden_layers"])
    if config["mlp_only_layers"] or int(config["decoder_sparse_step"]) != 1:
        raise ValueError("every layer of this family has the expert layer")
    return TransformerConfig(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]), n_layers=layers,
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        d_head=int(config["head_dim"]),
        d_ff=int(config["intermediate_size"]),
        d_expert=int(config["moe_intermediate_size"]),
        max_seq=2 * int(traffic["sentence_tokens"]),
        attention=train["attention"], attn_block=int(train["attn_block"]),
        loss_chunk=int(train["loss_chunk"]),
        remat=bool(train["remat"]), remat_policy=train["remat"] or "full",
        n_experts=int(config["published"]["num_experts"]),
        moe_top_k=int(config["num_experts_per_tok"]),
        experts_held=tuple(config["experts_held"]),
        router="softmax", expert_gated=True, qk_norm=True,
        layer_ops=("attention",) * layers, layer_ffns=("moe",) * layers,
        norm_eps=float(config["rms_norm_eps"]),
        rope_base=float(config["rope_theta"]),
        init_std=float(config["initializer_range"]),
        tied_head=bool(config["tie_word_embeddings"]),
        objective="block_diffusion",
        diffusion_block=int(d["block_length"]),
        mask_token=int(d["mask_token_id"]), noise_eps=float(d["noise_eps"]),
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
        # token ids: the vocabulary slice less the id that stands for <MASK>
        self.vocab = int(config["diffusion"]["mask_token_id"])
        if self.vocab != int(config["vocab_size"]) - 1:
            raise ValueError("<MASK> stands at the slice's last id")
        self.dims = reference.dims(config)
        self.tie_gap = float(config["check"]["tie_gap"])
        self.counters = []
        self.cache_dir = None
        self.steps_done = 0       # == int(state.step): the next noise key

    # -- inputs -----------------------------------------------------------------
    def make_inputs(self) -> None:
        """The token stream as packed sequences, from ``STREAM_SEED`` (see
        there); from ``--seed``, ``eval_tokens`` / S held-out sequences of
        the same law and one batch for the half-layer check.  Token id = the
        key's rank, as in ``lm.Family``."""
        ranks, _offsets = traffic_gen.key_stream(self.traffic, self.vocab,
                                                 STREAM_SEED)
        n = len(ranks) // self.seq_len
        self.sequences = ranks[:n * self.seq_len].reshape(n, self.seq_len)
        if n < self.seqs:
            raise ValueError(f"the stream holds {n} sequences, a step "
                             f"needs {self.seqs}")
        rng = np.random.default_rng([self.seed, 0x1F32])
        p = traffic_gen.rank_probabilities(self.traffic["keys"], self.vocab)
        held = int(self.traffic["eval_tokens"]) // self.seq_len
        self.held_out = traffic_gen.draw_ranks(
            rng, p, held * self.seq_len).reshape(held, self.seq_len)
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
        self._noise = jax.jit(self._program_noise)
        self._trunk = jax.jit(self._program_hidden)
        self.tiles = self._attention_tiles() if self.telemetry else {}

    def _fixed(self) -> list:
        """What no step may move: the routers of a chip's share."""
        return [np.asarray(g["moe"].router)
                for g in self.state.params["blocks"]]

    def _program_noise(self, step, tokens):
        """The noise the step numbered ``step`` draws for ``tokens``, by the
        program's own function and key."""
        from swiftmpi_tpu.models.diffusion import block_noise

        return block_noise(self.trainer.noise_key(step), tokens, self.cfg)

    def _attention_tiles(self) -> dict:
        """``attn_pair_fill_share``: the program's own mask walked tile by
        tile at the step's tile size — the pairs it lets through over the
        pairs in the tiles it lists.  Raises unless the lists reach every
        visible pair of the reference's boolean mask."""
        from swiftmpi_tpu.models.diffusion import BlockDiffusionMask

        S, size = self.seq_len, min(self.cfg.attn_block, self.seq_len)
        mask = BlockDiffusionMask(S, self.cfg.diffusion_block)
        n, pos = 2 * S // size, np.arange(size)
        seen = folded = 0
        for i in range(n):
            lo, hi, tile = mask.key_tiles(i, n, size)
            for t in range(int(lo), int(hi)):
                j = int(tile(t))
                seen += int(np.asarray(mask.visible(
                    (i * size + pos)[:, None], (j * size + pos)[None])).sum())
                folded += size * size
        want = S * S + S * self.cfg.diffusion_block
        if seen != want:
            raise AssertionError(f"the mask's tile lists reach {seen} of "
                                 f"{want} visible pairs")
        return {"attn_pair_fill_share": 100.0 * seen / folded}

    def step_shape(self, chips: int) -> dict:
        """What ``costs/bdlm.py`` counts from; ``held_pick_share`` is the
        median the traced chunks counted, else a uniform router's mean."""
        c, (lo, hi) = self.config, self.config["experts_held"]
        experts = int(c["published"]["num_experts"])
        shares = [m["held_pick_share"] for m in self.counters
                  if "held_pick_share" in m]
        return {"tokens": self.seqs * self.seq_len, "seq_len": self.seq_len,
                "layers": int(c["num_hidden_layers"]),
                "d_model": int(c["hidden_size"]),
                "heads": int(c["num_attention_heads"]),
                "kv_heads": int(c["num_key_value_heads"]),
                "d_head": int(c["head_dim"]),
                "d_expert": int(c["moe_intermediate_size"]),
                "experts": experts, "experts_held": hi - lo,
                "top_k": int(c["num_experts_per_tok"]),
                "vocab": int(c["vocab_size"]),
                "diffusion_block": int(c["diffusion"]["block_length"]),
                "attn_block": int(c["train"]["attn_block"]),
                "held_pick_share": float(np.median(shares)) if shares
                else 100.0 * (hi - lo) / experts,
                "parameters": self._parameters(), "chips": chips}

    def run_chunk(self, steps: int):
        """One ``Trainer.run`` over the next ``steps`` batches, fenced on
        the parameters.  Returns (tokens trained, the last step's loss).
        A traced run also counts the masked positions of the batches it
        feeds, from the steps' noise drawn again before the call."""
        import jax

        batches = [self.next_batch() for _ in range(steps)]
        counted = {}
        if self.telemetry:
            masked = sum(int((self._noise(self.steps_done + i, b)[1] > 0)
                             .sum()) for i, b in enumerate(batches))
            counted = {"pairs_per_step": masked / steps,
                       "pair_fill_share": 100.0 * masked
                       / sum(b.size for b in batches)}
        with self.annotate("bench/train_call"):
            self.state, losses = self.trainer.run(self.state, iter(batches))
        with self.annotate("bench/fence"):
            jax.block_until_ready(self.state.params)
        self.steps_done += steps
        program = {k: v for k, v in self.trainer.train_metrics.items()
                   if isinstance(v, (int, float))}
        self.counters.append({**counted, **self.tiles, **program})
        return steps * self.seqs * self.seq_len, float(losses[-1])

    # -- correctness ----------------------------------------------------------
    def _sampled(self, tree) -> dict:
        """Host copies of one tensor (or sampled rows) of every kind: the
        first layer's router, one held expert's three matrices, QK-norm
        gains and attention matrices, the final gain, embedding and head
        rows the first batch saw and rows it did not."""
        g = tree["blocks"][0]
        e = self.seed % g["moe"].w_in.shape[1]          # a held expert
        out = {"q_norm": g["q_norm"][0], "k_norm": g["k_norm"][0],
               "wq": g["wq"][0, :ROW_SAMPLE], "wk": g["wk"][0, :ROW_SAMPLE],
               "wo": g["wo"][0, :ROW_SAMPLE],
               "router": g["moe"].router[0],
               "expert_w1": g["moe"].w_gate[0, e, :ROW_SAMPLE],
               "expert_w3": g["moe"].w_in[0, e, :ROW_SAMPLE],
               "expert_w2": g["moe"].w_out[0, e, :ROW_SAMPLE],
               "ln_f": tree["ln_f"],
               "embed_seen": tree["embed"][self.rows_seen],
               "embed_unseen": tree["embed"][self.rows_unseen],
               "head_seen": tree["head"][self.rows_seen],
               "head_unseen": tree["head"][self.rows_unseen]}
        return {k: np.asarray(v) for k, v in out.items()}

    def _seed_key(self, tag=None):
        """A key of ``--seed`` (any whole number to a little over 2**31):
        the held-out sequences' noise, or with ``tag`` another draw."""
        import jax

        key = jax.random.fold_in(jax.random.key(self.seed % 2 ** 31),
                                 self.seed // 2 ** 31)
        return key if tag is None else jax.random.fold_in(key, tag)

    def _program_hidden(self, params, noisy, batch):
        """The program's residual stream at every half layer of ``[noisy ;
        batch]``."""
        from swiftmpi_tpu.models.diffusion import attention_inputs, trunk_input
        from swiftmpi_tpu.models.transformer import hidden_states

        return hidden_states(params, trunk_input(noisy, batch), self.cfg,
                             **attention_inputs(self.seq_len, self.cfg))

    def _hidden(self, noisy, batch) -> list:
        """:meth:`_program_hidden` staged on the host, as ``families/lm.py``
        does: 9 x 268 MB."""
        return [np.asarray(h) for h in self._trunk(self.state.params, noisy,
                                                   batch)]

    def first_step_check(self) -> dict:
        """Hold the program to the plain reference at the timed sizes.
        From ``--seed``: every half layer on the program's own input, for
        a batch and a noise draw of the seed (``check_batch``).  From the
        timed first step itself (the stream's first batch): its noise
        drawn again and the reference's own noise function held equal to
        it, the loss the step returned against the reference's own forward
        pass, and the gradient it left in AdamW's first moment against the
        reference's backward pass."""
        from swiftmpi_tpu.models.diffusion import block_noise

        params = self.state.params
        c_noisy = np.asarray(block_noise(self._seed_key(1), self.check_batch,
                                         self.cfg)[0])
        hs = self._hidden(c_noisy, self.check_batch)
        layer = self._half_layer_check(params, self.check_batch, hs)
        del hs

        batch = self.sequences[(self._next + np.arange(self.seqs))
                               % len(self.sequences)]
        noisy, weights = (np.asarray(a) for a in
                          self._noise(self.steps_done, batch))
        ref_noisy, ref_weights = (np.asarray(a) for a in reference.noise(
            self.trainer.noise_key(self.steps_done), batch, self.dims))
        noise_off = float(np.mean((noisy != ref_noisy) | ~np.isclose(
            weights, ref_weights, rtol=1e-5)))
        seen = np.unique(np.concatenate([noisy.ravel(), batch.ravel()]))
        unseen = np.setdiff1d(np.arange(int(self.config["vocab_size"])), seen)
        rng = np.random.default_rng([self.seed, 0xF4EE])
        # rows the loss named as targets at masked positions, and rows no
        # position of the batch read or predicted
        targets = np.unique(batch[weights > 0])
        self.rows_seen = rng.choice(targets, min(ROW_SAMPLE, len(targets)),
                                    False)
        self.rows_unseen = rng.choice(unseen, min(ROW_SAMPLE, len(unseen)),
                                      False) if len(unseen) else seen[:1]
        hs = self._hidden(noisy, batch)
        loss_ref = self.ref.loss(params, batch, noisy, weights)
        _loss_at, grads = self.ref.loss_and_grads(params, batch, noisy,
                                                  weights, at=hs)
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
                           "limit": LIMITS["loss"]},
                  "noise": {"max_err": noise_off, "limit": LIMITS["noise"]}}
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
              f"{loss_ref:.6f}; {int((weights > 0).sum())} of {batch.size} "
              f"positions masked; clip scale {scale:.4f}; "
              f"{100 * layer['tie_share']:.3f}% of expert-layer tokens left "
              f"out as near ties (gap < {self.tie_gap}); limits {LIMITS}",
              flush=True)
        return {"ok": all(f["ok"] for f in fields.values())
                and bool(np.isfinite(loss)), "fields": fields, "loss": loss,
                "rows_checked": int(2 * self.check_batch.size),
                "train_call_s": train_call_s, "sampler_max_abs_err": 0.0}

    def eval_loss(self):
        """The block-diffusion loss of the held-out sequences under noise
        drawn from ``--seed`` by the reference's own noise function, by the
        plain reference on the parameters as they stand.  Twice: the
        harness unpacks two values and this family has no second
        objective."""
        noisy, weights = reference.noise(self._seed_key(), self.held_out,
                                         self.dims)
        each = self.ref.sequence_losses(self.state.params, self.held_out,
                                        noisy, weights)
        print("[bench] held-out loss by sequence: "
              + " ".join(f"{x:.4f}" for x in each), flush=True)
        return float(np.mean(each)), float(np.mean(each))
