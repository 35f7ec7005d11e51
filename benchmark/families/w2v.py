"""The word2vec family: a model built and fed exactly as ``apps/w2v_main.py``
does it — conf file -> ``global_config().load_conf`` -> ``Word2Vec(seed)``
-> ``build_from_vocab`` -> ``train(batcher=...)`` over the native
``PrefetchingCBOWBatcher`` — from a configuration and a traffic mix.

The conf holds only the configuration's ``[word2vec]`` / ``[server]``
hyperparameters and the traffic's ``[worker] minibatch`` (plus ``telemetry``
in a traced run): every mechanism of the program stays at its default, so a
mechanism shows in the ledger when a PR makes the program choose it.

Every attribute of the program this file touches is listed in
``benchmark/README.md``.
"""

from __future__ import annotations

import os
import time

import numpy as np

from ..lib import traffic as traffic_gen
from ..reference import w2v as reference

ROW_SAMPLE = 512


class ChunkBatcher:
    """``train()`` is epoch-shaped; this makes an epoch a *chunk*: the next
    ``steps`` full batches of the repo batcher's cycling stream.  One inner
    ``epoch()`` generator stays open across calls (a new one is opened when
    the stream is spent), partial tail batches are skipped so no second
    shape compiles, and ``epoch_stencil`` / ``vocab`` are forwarded the
    same way, so a program that picks another rendering still runs."""

    def __init__(self, inner, annotate):
        self.inner = inner
        self.vocab = inner.vocab
        self.steps = 1
        self.last = None          # the newest batch handed to train()
        self._annotate = annotate
        self._gens = {}
        self._held = {}           # (kind, batch_size) -> peeked batch

    def _next_full(self, kind: str, batch_size: int):
        key = (kind, batch_size)
        held = self._held.pop(key, None)
        if held is not None:
            return held
        for _ in range(3):    # the rest of the open epoch, then two whole ones
            if key not in self._gens:
                self._gens[key] = iter(getattr(self.inner, kind)(batch_size))
            for batch in self._gens[key]:
                if batch.n_words == batch_size:
                    return batch
            del self._gens[key]
        raise RuntimeError(
            f"the stream has no full batch of {batch_size} centers")

    def peek(self, kind: str, batch_size: int):
        """The batch the next ``epoch(batch_size)`` will yield first."""
        key = (kind, batch_size)
        if key not in self._held:
            self._held[key] = self._next_full(kind, batch_size)
        return self._held[key]

    def _chunk(self, kind: str, batch_size: int):
        for _ in range(self.steps):
            with self._annotate("bench/next_batch"):
                self.last = self._next_full(kind, batch_size)
            yield self.last

    def epoch(self, batch_size: int):
        return self._chunk("epoch", batch_size)

    def epoch_stencil(self, batch_size: int):
        return self._chunk("epoch_stencil", batch_size)

    def close(self) -> None:
        for gen in self._gens.values():
            gen.close()           # stops the native prefetch thread
        self._gens.clear()


def as_cbow(batch, window: int):
    """(centers, contexts, mask) of a ``CBOWBatch`` or, by the expansion
    its docstring states, of a ``StencilBatch``."""
    if hasattr(batch, "contexts"):
        return (np.asarray(batch.centers), np.asarray(batch.contexts),
                np.asarray(batch.ctx_mask, bool))
    tokens, sent = np.asarray(batch.tokens), np.asarray(batch.sent_id)
    pos, half = np.asarray(batch.center_pos), np.asarray(batch.half)
    B, S = len(pos), len(tokens)
    contexts = np.zeros((B, 2 * window), np.int32)
    mask = np.zeros((B, 2 * window), bool)
    for i in range(B):
        js = [j for j in range(pos[i] - half[i], pos[i] + half[i] + 1)
              if j != pos[i] and 0 <= j < S and sent[j] == sent[pos[i]]]
        contexts[i, :len(js)] = tokens[js]
        mask[i, :len(js)] = True
    return tokens[pos], contexts, mask


class Family:
    def __init__(self, config: dict, traffic: dict, seed: int, workdir: str,
                 telemetry: bool, annotate):
        self.config, self.traffic = config, traffic
        self.seed, self.workdir = int(seed), workdir
        self.telemetry, self.annotate = telemetry, annotate
        w = config["word2vec"]
        self.window, self.negative = int(w["window"]), int(w["negative"])
        self.len_vec = int(w["len_vec"])
        self.minibatch = int(traffic.get("minibatch")
                             or traffic["centers_per_step"] * 2 * self.window)
        # the program's own rule (Word2Vec.train): first_step_check raises
        # if train() asks the batcher for another size
        self.centers = max(256, self.minibatch // (2 * self.window))
        self.alpha = float(w["learning_rate"])
        self.counters = []        # train_metrics of every chunk run

    # -- inputs, from the seed ------------------------------------------------
    def make_inputs(self) -> None:
        """Vocabulary and token stream in numpy: no corpus text, no parse."""
        from swiftmpi_tpu.data.text import Vocab

        V = int(self.config["vocab_size"])
        ranks, self.offsets = traffic_gen.key_stream(self.traffic, V,
                                                     self.seed)
        counts = np.bincount(ranks, minlength=V).astype(np.int64)
        rng = np.random.default_rng([self.seed, 0xC0DE])
        key_of_rank = rng.permutation(V).astype(np.uint64) + np.uint64(1)
        # both of the program's loaders order a vocabulary by
        # (count descending, key ascending)
        order = np.lexsort((key_of_rank, -counts))
        index_of_rank = np.empty(V, np.int32)
        index_of_rank[order] = np.arange(V, dtype=np.int32)
        self.tokens = index_of_rank[ranks]
        keys = key_of_rank[order]
        self.vocab = Vocab(keys, counts[order],
                           dict(zip(keys.tolist(), range(V))))

    # -- the w2v_main call sequence ---------------------------------------------
    def write_conf(self) -> str:
        c = self.config
        lines = ["[word2vec]"]
        lines += [f"{k}: {v}" for k, v in c["word2vec"].items()]
        lines += ["[server]"]
        lines += [f"{k}: {v}" for k, v in c["server"].items()]
        lines += ["[worker]", f"minibatch: {self.minibatch}"]
        if self.telemetry:
            # spans only: the JSONL sink goes to the run's temporary dir
            lines += ["telemetry: 1", "telemetry_path: "
                      + os.path.join(self.workdir, "telemetry.jsonl")]
        path = os.path.join(self.workdir, "cell.conf")
        with open(path, "w") as f:
            f.write("\n".join(lines) + "\n")
        return path

    def build_model(self) -> None:
        from swiftmpi_tpu.data import native
        from swiftmpi_tpu.models.word2vec import Word2Vec
        from swiftmpi_tpu.utils import global_config, reset_global_config
        from swiftmpi_tpu.utils.xla_env import ensure_compile_cache

        if not native.available():
            raise RuntimeError("the native loader did not build; there is "
                               "no Python-batcher fallback in a cell")
        self.cache_dir = ensure_compile_cache()
        reset_global_config()
        global_config().load_conf(self.write_conf()).parse()
        self.model = Word2Vec(seed=self.seed)
        self.model.build_from_vocab(self.vocab)
        self.batcher = ChunkBatcher(native.PrefetchingCBOWBatcher(
            self.tokens, self.offsets, self.vocab, self.model.window,
            self.model.sample, seed=2008 + self.seed), self.annotate)
        table = self.model.table
        self.slot_of = table.key_index.lookup(self.vocab.keys)
        free = np.ones(table.capacity, bool)
        free[self.slot_of] = False
        rng = np.random.default_rng([self.seed, 0xF4EE])
        free = np.flatnonzero(free)
        self.free_slots = rng.choice(free, min(ROW_SAMPLE, len(free)),
                                     replace=False).astype(np.int32)
        self.free_before = self.rows(self.free_slots)

    def step_shape(self, chips: int) -> dict:
        return {"centers": self.centers, "window": self.window,
                "negative": self.negative, "len_vec": self.len_vec,
                "chips": chips}

    def run_chunk(self, steps: int):
        """One ``train(niters=1)`` over the next ``steps`` full batches,
        fenced on the table state.  Returns (center words, loss)."""
        import jax

        self.batcher.steps = steps
        with self.annotate("bench/train_call"):
            losses = self.model.train(batcher=self.batcher, niters=1)
        with self.annotate("bench/fence"):
            jax.block_until_ready(self.model.table.state)
        self.counters.append({k: v for k, v in
                              self.model.train_metrics.items()
                              if isinstance(v, (int, float))})
        return steps * self.centers, float(losses[0])

    def close(self) -> None:
        if getattr(self, "batcher", None) is not None:
            self.batcher.close()

    # -- reading the table ----------------------------------------------------------
    def rows(self, slots, fields=None) -> dict:
        """Host copy of ``fields`` (default: all) at ``slots``.  The index
        is padded to a bucket so that the gather program's shape, and with
        it the compile cache's key, does not change with the seed."""
        import jax.numpy as jnp

        n = len(slots)
        idx = jnp.asarray(self._bucketed(slots))
        state = self.model.table.state
        return {f: np.asarray(state[f][idx])[:n] for f in fields or state}

    def _bucketed(self, slots) -> np.ndarray:
        """``slots`` padded to a whole number of buckets with an unoccupied
        slot, which no step writes."""
        n = len(slots)
        bucket = ROW_SAMPLE if n <= 8 * ROW_SAMPLE else 32768
        out = np.full(-(-n // bucket) * bucket, self.free_slots[0], np.int32)
        out[:n] = slots
        return out

    def placement(self, platform: str) -> dict:
        """Where the table is: every field on ``platform`` devices, its
        rows split evenly over the table axis."""
        model = self.model
        n = int(model.cluster.mesh.shape[model.cluster.table_axis])
        out = {"ok": True, "table_bytes": 0, "shards": n, "why": []}
        per_device = {}
        for f, a in model.table.state.items():
            plats = {d.platform for d in a.sharding.device_set}
            rows = {s.data.shape[0] for s in a.addressable_shards}
            if plats != {platform}:
                out["why"].append(f"field {f!r} lives on {sorted(plats)}")
            if rows != {a.shape[0] // n} or a.shape[0] % n:
                out["why"].append(f"field {f!r}: shard rows {sorted(rows)} "
                                  f"of {a.shape[0]} over {n}")
            out["table_bytes"] += a.nbytes
            for s in a.addressable_shards:
                per_device[s.device.id] = per_device.get(s.device.id, 0) \
                    + s.data.nbytes
        out["table_bytes_per_device"] = max(per_device.values())
        out["ok"] = not out["why"]
        return out

    # -- correctness ---------------------------------------------------------------
    def _negatives(self, key, shape):
        """The negatives the next step will draw: the program's own sampler
        with the key ``train()`` will split off — input generation, not the
        arithmetic under test."""
        import jax

        from swiftmpi_tpu.ops.sampling import sample_alias

        with reference.host_f32():
            sub = jax.random.split(jax.random.wrap_key_data(
                np.asarray(jax.random.key_data(key))))[1]
            return np.asarray(sample_alias(
                sub, np.asarray(self.model._alias_prob),
                np.asarray(self.model._alias_idx), shape))

    def sampler_error(self) -> float:
        """Largest absolute difference between the distribution the
        program's alias tables encode and unigram^0.75 of the counts."""
        prob = np.asarray(self.model._alias_prob, np.float64)
        alias = np.asarray(self.model._alias_idx)
        V = len(prob)
        p = (prob + np.bincount(alias, 1.0 - prob, minlength=V)) / V
        want = self.vocab.counts.astype(np.float64) ** 0.75
        return float(np.abs(p - want / want.sum()).max())

    def _target_layout(self, centers, contexts, mask, negs):
        t_words = np.concatenate([centers[:, None], negs], axis=1)
        t_valid = np.concatenate([np.ones((len(centers), 1), bool),
                                  negs != centers[:, None]], axis=1)
        t_valid &= mask.any(axis=1)[:, None]
        t_rows, t_ids = np.unique(self.slot_of[t_words], return_inverse=True)
        c_rows, c_ids = np.unique(self.slot_of[contexts],
                                  return_inverse=True)
        # bucketed, so that the reference's programs keep their shapes
        # (and their place in the compile cache) from seed to seed
        return (self._bucketed(t_rows), t_ids.reshape(t_words.shape),
                t_valid, self._bucketed(c_rows),
                c_ids.reshape(contexts.shape), mask)

    def first_step_check(self) -> dict:
        """Run the first train step as a one-step chunk and hold the rows
        it touched to the plain reference."""
        kind = "epoch_stencil" if getattr(self.model, "stencil", 0) \
            else "epoch"
        batch = self.batcher.peek(kind, self.centers)
        centers, contexts, mask = as_cbow(batch, self.window)
        negs = self._negatives(self.model._key,
                               (len(centers), self.negative))
        t_rows, t_ids, t_valid, c_rows, c_ids, c_valid = \
            self._target_layout(centers, contexts, mask, negs)
        def touched():
            return {**self.rows(t_rows, ("h", "h2sum")),
                    **self.rows(c_rows, ("v", "v2sum"))}

        before = touched()
        t0 = time.perf_counter()
        _words, loss = self.run_chunk(1)
        train_call_s = time.perf_counter() - t0
        if self.batcher.last is not batch:
            raise RuntimeError("train() did not take the peeked batch: the "
                               "program's batch rule changed (README.md)")
        want = reference.step(
            before, t_ids, t_valid, c_ids, c_valid, alpha=self.alpha,
            lr=float(self.config["server"]["initial_learning_rate"]))
        fields = reference.compare(touched(), want, before)
        # rows the whole run must have moved: the first step's contexts
        live = np.flatnonzero(np.bincount(
            c_ids[c_valid], minlength=len(c_rows)))[:ROW_SAMPLE]
        self.live_slots = c_rows[live]
        self.live_before = {"v": before["v"][live]}
        return {"ok": all(f["ok"] for f in fields.values())
                and np.isfinite(loss), "fields": fields, "loss": loss,
                "rows_checked": int(t_ids.max() + c_ids.max() + 2),
                "train_call_s": train_call_s,
                "sampler_max_abs_err": self.sampler_error()}

    def rows_check(self) -> dict:
        """Unoccupied rows bit-identical, sampled live rows moved and
        finite (``chip_smoke.check_rows``)."""
        free_after = self.rows(self.free_slots)
        same = all(np.array_equal(free_after[f], self.free_before[f])
                   for f in free_after)
        live = self.rows(self.live_slots)
        moved = np.any(live["v"] != self.live_before["v"], axis=1)
        finite = all(np.isfinite(a).all() for a in live.values())
        return {"ok": bool(same and moved.all() and finite),
                "unoccupied_unchanged": bool(same),
                "live_rows_moved": f"{int(moved.sum())}/{len(moved)}",
                "finite": bool(finite)}

    def eval_loss(self):
        """(error, ns) of a held-out batch drawn from the seed, on the table
        as it stands (``reference.held_out_loss``): the benchmark's own
        numbers, not what the program returns.  Full windows, ``negative``
        draws a center from unigram^0.75 of the vocabulary's counts."""
        rng = np.random.default_rng([self.seed, 0xE7A1])
        n, W, K = int(self.traffic["eval_centers"]), self.window, \
            self.negative
        pos = rng.integers(0, len(self.tokens), n)
        sent = np.searchsorted(self.offsets, pos, side="right") - 1
        lo, hi = self.offsets[sent], self.offsets[sent + 1]
        off = np.concatenate([np.arange(-W, 0), np.arange(1, W + 1)])
        ctx_pos = pos[:, None] + off[None, :]
        mask = (ctx_pos >= lo[:, None]) & (ctx_pos < hi[:, None])
        contexts = np.where(mask, self.tokens[np.clip(
            ctx_pos, 0, len(self.tokens) - 1)], 0)
        centers = self.tokens[pos]
        p = self.vocab.counts.astype(np.float64) ** 0.75
        negs = traffic_gen.draw_ranks(rng, p / p.sum(), n * K).reshape(n, K)
        t_rows, t_ids, t_valid, c_rows, c_ids, c_valid = \
            self._target_layout(centers, contexts, mask, negs)
        return reference.held_out_loss(
            self.rows(t_rows, ("h",))["h"], self.rows(c_rows, ("v",))["v"],
            t_ids, t_valid, c_ids, c_valid, alpha=self.alpha)
