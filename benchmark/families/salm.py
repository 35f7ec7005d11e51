"""The sparse-attention language-model family: ``families/lm.py``'s call
sequence — ``TransformerConfig`` -> ``Trainer(cfg, **optimizer).init_state
(key)`` -> ``Trainer.run(state, host batches)`` on packed sequences cut from
the traffic mix's token stream — for a stack whose attention is **learned
sparse attention** (``layer_ops = ("sparse", ...)``): an indexer scores every
earlier key, each query attends its top ``index_topk``, and the objective is
the next-token cross entropy plus the layers' index losses.

What this family touches in the program beyond what ``families/lm.py``'s and
``families/bdlm.py``'s heads list (``tests/test_benchmark_rehearsal.py::
test_harness_surface[salm_*]`` pins it): the ``TransformerConfig`` fields
``index_heads``, ``index_head_dim``, ``index_topk``; the kind ``"sparse"`` of
``layer_ops``; the parameter names ``wq_idx``, ``wk_idx``, ``w_idx``,
``idx_ln_g``, ``idx_ln_b``; ``models.transformer.sparse_probe(blk, x, cfg)``
(the indexer's ``qi``, ``w``, ``ki``, the selection's packed ``bits``, the
layer's ``index_loss`` and ``kept``, for one layer's input);
``parallel.sparse_attention.index_tile(qi, w, ki)`` and ``.unpack(bits,
tile)``; ``Trainer.train_metrics``'s ``main_loss``, ``index_loss``,
``index_loss_per_layer``, ``selected_keys_per_query``, ``selected_pair_share``
(telemetry on: ``obs.set_enabled``, which the first-step check turns on for
its one step whatever the run); the device scopes ``sparse_attention``, ``indexer``,
``index_select``.

The all-cell metrics read here as in ``families/lm.py``: a "pair" is a
position with a next token.
"""

from __future__ import annotations

import time

import numpy as np

from ..lib import traffic as traffic_gen
from ..reference import salm as reference
from . import lm
from .lm import ROW_SAMPLE, WEIGHTS_KEY, _rel, trainer_kwargs  # noqa: F401

#: the seed of the token stream the trainer is fed (the steps that train, the
#: warm-up and the timed window), whatever ``--seed``, as in
#: ``families/bdlm.py``, ``swlm.py``, ``mlalm.py`` and ``sslm.py`` and for
#: their reason: a step's time follows its batch (the held experts' share of
#: a layer's picks moves with the stream's hot tokens), so every run trains
#: on and times the same batches and a change is compared with its parent on
#: equal steps.  ``--seed`` makes the batch of the half-layer and selection
#: checks, the query blocks they sample, and the held-out sequence.
STREAM_SEED = 49
#: query rows of one sampled block of the index-score and selection checks,
#: and how many blocks a layer (drawn from ``--seed`` among the blocks whose
#: queries drop keys, where there are such)
INDEX_ROWS, INDEX_BLOCKS = 256, 2
#: limits of the first-step comparison.  Each lies between two readings on
#: the chip at the timed sizes (my chip runs, PR 49; PERF.md section 6;
#: ``tools/salm_lower_precision.py``): what the program gives with the bf16
#: operands and the float32 index sums, statistics, loss and exact selection
#: the configuration states against the plain reference, the largest over the
#: seeds run, and what the reference gives against itself with float8-e4m3
#: operands, which fails every limit below but ``ties`` (that one is a count
#: of what the others leave out), or under the control named beside the limit.
LIMITS = {
    # |L_step - L_reference| / L_reference of the objective the timed first
    # step returned, the reference under the program's selections: reading
    # 5.9e-5 on every seed (the first step's batch and the weights are
    # fixed; the reference with bf16-rounded operands 6.5e-5); a bf16 head
    # softmax and loss 3.25e-4, the next-token loss over half the sequence
    # 7.7e-4, float8 6.4e-3, the index loss at half its weight 9.0e-3.
    # (bf16 attention statistics 6.5e-6 and a bf16 index sum 2e-7 lie
    # inside the sound reading: PERF.md section 7)
    "loss": 1.4e-4,
    # |LI_program - LI_reference| / LI_reference of a layer on the program's
    # input and selection, the largest over the layers: readings 5.1e-4 -
    # 2.14e-3 (the reference with bf16-rounded operands 1.45e-3); float8
    # 8.8e-3.  ``step.index_loss``, the layers' sum the timed step itself
    # returned against the reference's under the step's selections, reads
    # this limit too: 7.15e-4 on every seed (bf16-rounded 6.7e-4); float8 0.37
    "index_loss": 4.5e-3,
    # index scores of the sampled query blocks: |I_program - I_reference|
    # over the root mean square of the query's causal reference scores, the
    # largest over the pairs: readings 4.2e-2 - 5.6e-2 (bf16-rounded 4.9e-2);
    # float8 0.54
    "index_scores": 0.15,
    # share of a sampled block's picks that differ from the reference's:
    # readings 7.6e-3 - 9.3e-3 (bf16-rounded 8.2e-3); float8 0.10.  (Of
    # those, the largest distance of a flipped key's reference score from
    # its query's reference threshold, in index_scores' unit, is held to the
    # configuration's ``check.index_tie_gap`` 0.09: readings 2.4e-2 - 3.4e-2,
    # bf16-rounded 2.2e-2; float8 0.31)
    "flips": 3e-2,
    # per token update error (reference/bdlm.py::update_error), per kind,
    # the reference under the program's selection: sparse 1.05e-2 - 3.48e-2
    # over 29 seeds (the largest of 65,536 tokens' errors a run: all but two
    # seeds under 2.1e-2, then 2.38e-2 and 3.48e-2, so the room is left above
    # the readings; bf16-rounded operands alone 5.7e-3: the program also
    # rounds the probabilities and the backward's score gradients), float8
    # 0.67; moe 5.3e-3 - 5.9e-3, float8 9.3e-2
    "sparse": 1e-1, "moe": 2.5e-2,
    # per sampled tensor, the Frobenius distance of AdamW's first moment
    # / (1 - b1) from the reference's clipped gradient, over its norm:
    # largest reading 9.0e-3, the indexer's wq_idx (bf16-rounded 9.0e-3);
    # float8 0.62 (wk_idx) - 1.01 (wq): its global norm moves the clip
    "grad": 5e-2,
    # share of tokens an expert layer may leave out as near ties (7.2e-4 -
    # 1.07e-3)
    "ties": 1e-2,
}


def transformer_config(config: dict, traffic: dict):
    """The program's ``TransformerConfig`` of a configuration file."""
    import jax.numpy as jnp

    from swiftmpi_tpu.models.transformer import TransformerConfig

    train, sa = config["train"], config["sa_config"]
    operands = config["precision"]["matmul_operands"]
    layers = int(config["num_hidden_layers"])
    if config["mlp_only_layers"] or int(config["decoder_sparse_step"]) != 1:
        raise ValueError("every layer of this family has the expert layer")
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("the indexer has one key a position")
    return TransformerConfig(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]), n_layers=layers,
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        d_head=int(config["head_dim"]),
        d_ff=int(config["intermediate_size"]),
        d_expert=int(config["moe_intermediate_size"]),
        max_seq=int(traffic["sentence_tokens"]),
        attention=train["attention"], attn_block=int(train["attn_block"]),
        loss_chunk=int(train["loss_chunk"]),
        remat=bool(train["remat"]), remat_policy=train["remat"] or "full",
        n_experts=int(config["published"]["num_experts"]),
        moe_top_k=int(config["num_experts_per_tok"]),
        experts_held=tuple(config["experts_held"]),
        router="softmax", expert_gated=True, qk_norm=True,
        layer_ops=("sparse",) * layers, layer_ffns=("moe",) * layers,
        index_heads=int(sa["indexer_num_heads"]),
        index_head_dim=int(sa["indexer_head_dim"]),
        index_topk=int(sa["topk"]),
        norm_eps=float(config["rms_norm_eps"]),
        rope_base=float(config["rope_theta"]),
        init_std=float(config["initializer_range"]),
        tied_head=bool(config["tie_word_embeddings"]),
        matmul_dtype=None if operands == "float32"
        else jnp.dtype(operands))


def index_errors(got, got_keep, want, want_keep) -> dict:
    """One query block's index scores ``got`` (R, S) and selection
    ``got_keep`` against the reference's ``want`` (``-inf`` at the non-causal
    pairs) and ``want_keep``: ``index_scores``, the largest ``|got - want|``
    over the root mean square of the query's causal reference scores;
    ``flips``, the share of the block's picks that differ; and
    ``index_tie_gap``, over those, the largest distance of the flipped key's
    reference score from the query's reference threshold (its smallest kept
    score), in the first's unit."""
    import jax.numpy as jnp

    causal = jnp.isfinite(want)
    rms = jnp.sqrt(jnp.sum(jnp.where(causal, want, 0.0) ** 2, -1)
                   / jnp.sum(causal, -1))[:, None]
    err = jnp.where(causal, jnp.abs(got - want), 0.0) / rms
    tau = jnp.min(jnp.where(want_keep, want, jnp.inf), -1)[:, None]
    flipped = (got_keep != want_keep) & causal
    gap = jnp.where(flipped, jnp.abs(want - tau), 0.0) / rms
    return {"index_scores": float(err.max()),
            "flips": float(flipped.sum()) / max(float(want_keep.sum()), 1.0),
            "index_tie_gap": float(gap.max())}


def verdict(readings: dict, limits: dict) -> dict:
    """The first-step comparison's fields: every reading beside its limit
    (a ``grad.<tensor>`` reads ``grad``'s, the step's own ``step.index_loss``
    reads ``index_loss``'s) and whether it holds.  ``tools/
    salm_lower_precision.py`` passes its controls' readings through this
    too."""
    fields = {}
    for name, err in readings.items():
        limit = limits["grad" if name.startswith("grad.")
                       else name.removeprefix("step.")]
        fields[name] = {"max_err": err, "limit": limit,
                        "ok": bool(np.isfinite(err) and err <= limit)}
    return fields


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
        self.index_tie_gap = float(config["check"]["index_tie_gap"])
        self.counters = []
        self.cache_dir = None

    # -- inputs -----------------------------------------------------------------
    def make_inputs(self) -> None:
        """The token stream as packed sequences, from ``STREAM_SEED`` (see
        there); from ``--seed``, one held-out sequence of the same law and
        one batch for the half-layer and selection checks."""
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
        from swiftmpi_tpu.models.transformer import (hidden_states,
                                                     sparse_probe)
        from swiftmpi_tpu.utils.xla_env import ensure_compile_cache

        self.cache_dir = ensure_compile_cache()
        if self.telemetry:
            obs.set_enabled(True)
        self.cfg = transformer_config(self.config, self.traffic)
        self.trainer = Trainer(self.cfg, **trainer_kwargs(self.config))
        self.state = self.trainer.init_state(jax.random.key(WEIGHTS_KEY))
        self.fixed = self._fixed()
        self.ref = reference.Reference(self.dims)
        self._trunk = jax.jit(lambda p, t: hidden_states(p, t, self.cfg))
        self._probe = jax.jit(lambda blk, x: sparse_probe(blk, x, self.cfg))

    def _fixed(self) -> list:
        """What no step may move: the routers of a chip's share."""
        return [np.asarray(g["moe"].router)
                for g in self.state.params["blocks"]]

    def step_shape(self, chips: int) -> dict:
        """What ``costs/salm.py`` counts from; ``held_pick_share`` and
        ``selected_keys_per_query`` are the medians the traced chunks
        counted, else a uniform router's mean and the closed form."""
        c, (lo, hi) = self.config, self.config["experts_held"]
        sa = c["sa_config"]
        experts = int(c["published"]["num_experts"])
        S, k = self.seq_len, int(sa["topk"])

        def counted(key, default):
            values = [m[key] for m in self.counters if key in m]
            return float(np.median(values)) if values else default

        return {"tokens": self.seqs * S, "seq_len": S,
                "layers": int(c["num_hidden_layers"]),
                "d_model": int(c["hidden_size"]),
                "heads": int(c["num_attention_heads"]),
                "kv_heads": int(c["num_key_value_heads"]),
                "d_head": int(c["head_dim"]),
                "d_expert": int(c["moe_intermediate_size"]),
                "experts": experts, "experts_held": hi - lo,
                "top_k": int(c["num_experts_per_tok"]),
                "vocab": int(c["vocab_size"]),
                "index_heads": int(sa["indexer_num_heads"]),
                "index_dim": int(sa["indexer_head_dim"]),
                "index_topk": k,
                "attn_block": int(c["train"]["attn_block"]),
                "held_pick_share": counted(
                    "held_pick_share", 100.0 * (hi - lo) / experts),
                "selected_keys_per_query": counted(
                    "selected_keys_per_query",
                    sum(min(t + 1, k) for t in range(S)) / S),
                "parameters": self._parameters(), "chips": chips}

    def run_chunk(self, steps: int):
        words, loss = super().run_chunk(steps)
        m = self.counters[-1]
        if "selected_pair_share" in m:
            print("[bench] chunk counters: " + " ".join(
                f"{k}={m[k]:.6g}" for k in (
                    "selected_keys_per_query", "selected_pair_share",
                    "main_loss", "index_loss", "held_pick_share",
                    "expert_load_max_over_mean", "dropped_picks_per_step")
                if k in m), flush=True)
        return words, loss

    # -- correctness ----------------------------------------------------------
    def _sampled(self, tree) -> dict:
        """Host copies of one tensor (or sampled rows) of every kind: the
        first layer's router, one held expert's three matrices, QK-norm
        gains and attention matrices, the indexer's three matrices and its
        LayerNorm, the final gain, embedding and head rows the first batch
        saw and rows it did not."""
        g = tree["blocks"][0]
        e = self.seed % g["moe"].w_in.shape[1]          # a held expert
        out = {"q_norm": g["q_norm"][0], "k_norm": g["k_norm"][0],
               "wq": g["wq"][0, :ROW_SAMPLE], "wk": g["wk"][0, :ROW_SAMPLE],
               "wo": g["wo"][0, :ROW_SAMPLE],
               "wq_idx": g["wq_idx"][0, :ROW_SAMPLE],
               "wk_idx": g["wk_idx"][0, :ROW_SAMPLE],
               "w_idx": g["w_idx"][0, :ROW_SAMPLE],
               "idx_ln_g": g["idx_ln_g"][0], "idx_ln_b": g["idx_ln_b"][0],
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

    def _hidden(self, batch) -> list:
        """The program's residual stream at every half layer of ``batch``,
        staged on the host as ``families/lm.py`` does."""
        return [np.asarray(h) for h in self._trunk(self.state.params, batch)]

    def _sparse_blocks(self, params) -> list:
        """One parameter tree per layer (every layer is sparse)."""
        return [blk for _kind, blk in reference.layers(params, self.dims)]

    def _probed(self, blk, x):
        """(``sparse_probe`` of one layer's input ``x`` (B, S, d), its
        selection unpacked: (B, S, S) bool on the device)."""
        import jax.numpy as jnp

        from swiftmpi_tpu.parallel.sparse_attention import unpack

        probe = self._probe(blk, jnp.asarray(x))
        return probe, unpack(probe["bits"],
                             min(self.cfg.attn_block, self.seq_len))

    def sampled_blocks(self, rng) -> list:
        """First rows of the query blocks the index checks sample."""
        rows = min(INDEX_ROWS, self.seq_len)
        starts = np.arange(0, self.seq_len, rows)
        # blocks whose queries drop keys, where there are such
        late = starts[starts + rows > self.dims["index_topk"]]
        pool = late if len(late) else starts
        return [int(r) for r in rng.choice(pool, min(INDEX_BLOCKS, len(pool)),
                                           False)]

    def _index_check(self, blk, x, probe, keep, rng) -> dict:
        """Checks (1) and (2) on sampled query blocks of one layer's input
        ``x`` (B, S, d) of sequence 0: the program's index scores and
        selection against the reference's (:func:`index_errors`), the worst
        over the blocks."""
        import jax.numpy as jnp

        from swiftmpi_tpu.parallel.sparse_attention import index_tile

        rows = min(INDEX_ROWS, self.seq_len)
        out = {"index_scores": 0.0, "flips": 0.0, "index_tie_gap": 0.0}
        for row0 in self.sampled_blocks(rng):
            want, want_keep = self.ref.index_block(blk, jnp.asarray(x[0]),
                                                   row0, rows)
            sl = slice(row0, row0 + rows)
            got = index_tile(probe["qi"][:1, sl], probe["w"][:1, sl],
                             probe["ki"][:1])[0]
            for name, v in index_errors(got, keep[0, sl], want,
                                        want_keep).items():
                out[name] = max(out[name], v)
        return out

    def _layer_checks(self, params, batch, hs, rng) -> dict:
        """Every layer on the program's own input: the indexer and the
        selection on sampled query blocks (:meth:`_index_check`), then the
        sparse half and the expert half against the reference *under the
        program's selection* (per-token update error; the layer's index
        loss), and the count of pairs the program kept against the closed
        form.  Returns the worst of each."""
        import jax.numpy as jnp

        S, k = self.seq_len, self.cfg.index_topk
        closed = batch.shape[0] * sum(min(t + 1, k) for t in range(S))
        worst = {"index_scores": 0.0, "flips": 0.0, "index_tie_gap": 0.0,
                 "index_loss": 0.0, "sparse": 0.0, "moe": 0.0}
        ties = n_moe = 0
        kept_ok = True
        for layer, blk in enumerate(self._sparse_blocks(params)):
            x, mid, out = hs[2 * layer], hs[2 * layer + 1], hs[2 * layer + 2]
            probe, keep = self._probed(blk, x)
            kept_ok &= int(probe["kept"]) == closed == int(keep.sum())
            for name, v in self._index_check(blk, x, probe, keep,
                                             rng).items():
                worst[name] = max(worst[name], v)
            index_loss = 0.0
            for b in range(batch.shape[0]):
                err, _gap, li = self.ref.half_error(
                    "sparse", blk, jnp.asarray(x[b]), jnp.asarray(mid[b]),
                    keep[b])
                worst["sparse"] = max(worst["sparse"], float(err.max()))
                index_loss += float(li) / batch.shape[0]
                err, gap, _li = self.ref.half_error(
                    "moe", blk, jnp.asarray(mid[b]), jnp.asarray(out[b]))
                near = gap < self.tie_gap
                ties += int(near.sum())
                n_moe += err.shape[0]
                worst["moe"] = max(worst["moe"], float(
                    jnp.where(near, 0.0, err).max()))
            worst["index_loss"] = max(
                worst["index_loss"],
                abs(float(probe["index_loss"]) - index_loss)
                / max(index_loss, 1e-30))
        return {"worst": worst, "tie_share": ties / max(n_moe, 1),
                "kept_ok": bool(kept_ok)}

    def first_step_check(self) -> dict:
        """Hold the program to the plain reference at the timed sizes.
        From ``--seed`` (``check_batch``, and the query blocks sampled):
        (1) the index scores and (2) the selection of sampled query blocks
        of every layer, on the program's own input to it; (3a) every half
        layer's update and every layer's index loss under the program's
        selection.  From the timed first step itself (the stream's first
        batch): (3b) the objective it returned against the reference's own
        forward pass under the program's selections (``loss``; the
        reference's under its own printed beside it), the index loss and the
        count of kept pairs *the step itself* returned beside its loss
        (``step.index_loss`` against the reference's under the program's
        selections; the count against the closed form), and the gradient it
        left in AdamW's first moment against the reference's backward pass
        at the program's inputs and selections."""
        from swiftmpi_tpu import obs

        params = self.state.params
        rng = np.random.default_rng([self.seed, 0xF4EE])
        hs = self._hidden(self.check_batch)
        layer = self._layer_checks(params, self.check_batch, hs, rng)
        worst = layer.pop("worst")
        del hs

        batch, hs, keeps = self.first_batch(rng)
        own = self.ref.losses(params, batch)
        given = self.ref.losses(params, batch, keeps)
        _at, grads = self.ref.loss_and_grads(params, batch, at=hs,
                                             keeps=keeps)
        del hs, keeps
        want = self.clipped(grads)
        del grads
        self.live_before = self._sampled(params)

        # the step's own index loss and count of kept pairs ride with its
        # loss when telemetry is on: on for this one step, whatever the run
        was_on = obs.get_registry().enabled
        obs.set_enabled(True)
        try:
            t0 = time.perf_counter()
            _words, loss = self.run_chunk(1)
            train_call_s = time.perf_counter() - t0
        finally:
            obs.set_enabled(was_on)
        step = self.counters[-1]
        b1 = float(self.config["optimizer"]["b1"])
        mu = self._sampled(self.state.opt_state[1][0].mu)
        closed = sum(min(t + 1, self.cfg.index_topk)
                     for t in range(self.seq_len)) / self.seq_len
        step_kept_ok = abs(step["selected_keys_per_query"] - closed) \
            <= 1e-6 * closed

        limits = {**LIMITS, "index_tie_gap": self.index_tie_gap}
        readings = {**worst, "ties": layer["tie_share"],
                    "loss": abs(loss - given[0]) / given[0],
                    "step.index_loss":
                    abs(step["index_loss"] - given[2]) / given[2],
                    **{"grad." + name: _rel(mu[name] / (1.0 - b1), g)
                       for name, g in want.items()}}
        fields = verdict(readings, limits)
        print(f"[bench] first step: objective {loss:.6f} = main "
              f"{step['main_loss']:.6f} + index {step['index_loss']:.6f}; "
              f"reference under the program's selections {given[0]:.6f} = "
              f"{given[1]:.6f} + {given[2]:.6f} (off by "
              f"{abs(loss - given[0]) / given[0]:.2e}), under its own "
              f"{own[0]:.6f} = {own[1]:.6f} + {own[2]:.6f} (off by "
              f"{abs(loss - own[0]) / own[0]:.2e}); pairs kept equal the "
              f"closed form: probed {layer['kept_ok']}, the step's "
              f"{step_kept_ok} ({step['selected_keys_per_query']:.4f} a "
              f"query); {100 * worst['flips']:.3f}% of a sampled "
              f"block's picks differ from the reference's, the farthest "
              f"{worst['index_tie_gap']:.2e} of the query's score rms from "
              f"its threshold; {100 * layer['tie_share']:.3f}% of "
              f"expert-layer tokens left out as near ties (gap < "
              f"{self.tie_gap}); limits {limits}", flush=True)
        return {"ok": all(f["ok"] for f in fields.values())
                and layer["kept_ok"] and bool(step_kept_ok)
                and bool(np.isfinite(loss)),
                "fields": fields, "loss": loss,
                "rows_checked": int(self.check_batch.size + batch.size),
                "train_call_s": train_call_s, "sampler_max_abs_err": 0.0}

    def first_batch(self, rng):
        """(The stream's next batch — what the timed first step trains on —,
        the program's half-layer inputs of it, its selections: a list over
        the layers of (B, S, S) bool); draws the embedding and head rows
        :meth:`_sampled` reads."""
        batch = self.sequences[(self._next + np.arange(self.seqs))
                               % len(self.sequences)]
        seen = np.unique(batch)
        unseen = np.setdiff1d(np.arange(self.vocab), seen)
        self.rows_seen = rng.choice(seen, min(ROW_SAMPLE, len(seen)), False)
        self.rows_unseen = rng.choice(unseen, min(ROW_SAMPLE, len(unseen)),
                                      False) if len(unseen) else seen[:1]
        hs = self._hidden(batch)
        keeps = [np.asarray(self._probed(blk, hs[2 * i])[1])
                 for i, blk in enumerate(
                     self._sparse_blocks(self.state.params))]
        return batch, hs, keeps

    def clipped(self, grads) -> dict:
        """:meth:`_sampled` of a gradient tree after the optimizer's
        global-norm clip: what AdamW's first moment / (1 - b1) holds after
        the first step."""
        clip = float(self.config["optimizer"]["grad_clip"])
        scale = min(1.0, clip / max(reference.global_norm(grads), 1e-30))
        return {k: scale * v for k, v in self._sampled(grads).items()}

    def eval_loss(self):
        """The whole objective ``L_LM + sum LI`` of the held-out sequence
        over the vocabulary slice, by the plain reference (its own
        selections) on the parameters as they stand — the benchmark's own
        number — with its two parts printed beside it; the harness's second
        value is ``L_LM``."""
        loss, main, index = self.ref.losses(self.state.params, self.held_out)
        print(f"[bench] held-out objective {loss:.6f} = main {main:.6f} + "
              f"index {index:.6f}", flush=True)
        return loss, main
