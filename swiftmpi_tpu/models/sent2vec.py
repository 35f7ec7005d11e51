"""sent2vec: paragraph-vector (PV-DM-style) inference over frozen word
vectors.

Re-design of `/root/reference/src/apps/sent2vec/sent2vec.cpp`: load a
pre-trained word2vec table (``load_word_vector`` → server load,
sent2vec.cpp:32-35), then for each sentence initialize a random sentence
vector and run ``niters`` gradient passes updating **only** that vector —
word gradients are never pushed (``WordMiniBatch::push() = delete``,
sent2vec.cpp:6-12).

Per position (sent2vec.cpp:108-181):
    neu1 = sent_vec + sum of context word v-vectors  (random-shrunk window)
    for target in {center(1), K negatives(0)}:  skip neg == center
        g = (label - sigmoid_clipped(neu1 . h_target)) * alpha
        neu1e += g * h_target
    sent_vec += alpha * neu1e          # note: alpha applied twice, as in
                                       # the reference (g already carries it)

TPU shape: sentences are batched ``(S, L)`` and the position loop is a
``lax.scan`` carrying ``sent_vec`` — bit-faithful sequential-within-pass
semantics, vectorized across the batch; fresh negatives are drawn on device
each pass like the reference redraws per ``learn_instance`` call.

Sentence ids are the BKDR hash of the raw line (sent2vec.cpp:75) and the
output format is ``sent_id\\tv0 v1 ...`` (sent2vec.cpp:82-86).
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from swiftmpi_tpu.data.text import tokenize
from swiftmpi_tpu.models.word2vec import Word2Vec
from swiftmpi_tpu.ops.sampling import (build_unigram_alias,
                                       sample_alias_slots)
from swiftmpi_tpu.ops.sigmoid import sigmoid_clipped
from swiftmpi_tpu.utils.config import ConfigParser
from swiftmpi_tpu.utils.hashing import bkdr_hash
from swiftmpi_tpu.utils.logger import get_logger
from swiftmpi_tpu.utils.timers import Error

log = get_logger(__name__)


class Sent2Vec:
    def __init__(self, word_model: Word2Vec,
                 config: Optional[ConfigParser] = None, seed: int = 0):
        """``word_model``: a Word2Vec whose table holds the frozen word
        vectors (train it, or ``load()`` a dump)."""
        self.config = config if config is not None else word_model.config
        g = self.config.get_or
        self.window = g("word2vec", "window", 4).to_int32()
        self.negative = g("word2vec", "negative", 20).to_int32()
        self.alpha = g("word2vec", "learning_rate", 0.05).to_float()
        self.batchsize = g("worker", "minibatch", 256).to_int32()
        self.word_model = word_model
        self.len_vec = word_model.len_vec
        self._key = jax.random.key(seed ^ 0xD0C)
        self._infer = None
        self.error = Error()
        # serving plane: attach a serve.SnapshotPublisher and
        # infer_sentences() publishes the finished sentence vectors as a
        # {"sent": (S, d)} snapshot keyed by sentence id — the top-k
        # query path then answers nearest-sentence queries
        self.serve_publisher = None

    # -- the jitted inference kernel ---------------------------------------
    def _build_infer(self):
        W, K, d, alpha = (self.window, self.negative, self.len_vec,
                          self.alpha)
        offsets = np.array([o for o in range(-W, W + 1) if o != 0],
                           np.int32)

        @partial(jax.jit, static_argnums=8)  # niters is a scan length
        def infer(h_table, v_table, word_slots, word_mask, alias_prob,
                  alias_idx, slot_of_vocab, vocab_of_pos, niters, key):
            """word_slots: (S, L) table slots; vocab_of_pos: (S, L) vocab
            ids (for neg==center masking); returns (S, d) sentence vecs."""
            S, L = word_slots.shape
            # table rows may be stored wider than the vector
            # (access.stored_width): the sentence vector is d wide
            V_all = jnp.take(v_table, jnp.maximum(word_slots, 0),
                             axis=0)[..., :d]
            V_all = V_all * word_mask[..., None]            # (S, L, d)
            k_init, key = jax.random.split(key)
            # Vec::random init, (U(0,1)-0.5)/len  (vec1.h:229-232)
            sent0 = (jax.random.uniform(k_init, (S, d)) - 0.5) / d

            def one_pass(carry, _):
                sent_vec, key = carry
                key, kb, kn = jax.random.split(key, 3)
                b = jax.random.randint(kb, (S, L), 0, W)    # window shrink
                # fused draw+slot lookup: (S, L, K) negatives per pass
                # is the dominant transaction count of the whole
                # inference — see ops/sampling.sample_alias_slots
                negs_v, neg_slots = sample_alias_slots(
                    kn, alias_prob, alias_idx, slot_of_vocab, (S, L, K))

                def pos_step(sv, p):
                    ctx_idx = p + offsets                    # (2W,)
                    in_range = (ctx_idx >= 0) & (ctx_idx < L)
                    ctx_idx_c = jnp.clip(ctx_idx, 0, L - 1)
                    ctx_v = V_all[:, ctx_idx_c, :]           # (S, 2W, d)
                    half = W - b[:, p]                       # (S,)
                    ok = (in_range[None, :]
                          & (jnp.abs(offsets)[None, :] <= half[:, None])
                          & word_mask[:, ctx_idx_c])
                    neu1 = sv + jnp.sum(ctx_v * ok[..., None], axis=1)
                    center_slot = word_slots[:, p]           # (S,)
                    t_slots = jnp.concatenate(
                        [center_slot[:, None], neg_slots[:, p, :]], axis=1)
                    h_t = jnp.take(h_table, jnp.maximum(t_slots, 0),
                                   axis=0)[..., :d]          # (S, K+1, d)
                    f = jnp.einsum("sd,skd->sk", neu1, h_t)
                    labels = jnp.concatenate(
                        [jnp.ones((S, 1)), jnp.zeros((S, K))], axis=1)
                    g = (labels - sigmoid_clipped(f)) * alpha
                    valid = jnp.concatenate(
                        [jnp.ones((S, 1), bool),
                         negs_v[:, p, :] != vocab_of_pos[:, p][:, None]],
                        axis=1) & word_mask[:, p][:, None]
                    g = jnp.where(valid, g, 0.0)
                    neu1e = jnp.einsum("sk,skd->sd", g, h_t)
                    sv = sv + alpha * neu1e
                    return sv, jnp.sum(g * g)

                sent_vec, gg = jax.lax.scan(
                    pos_step, sent_vec, jnp.arange(L))
                return (sent_vec, key), jnp.sum(gg)

            (sent_vec, _), errs = jax.lax.scan(
                one_pass, (sent0, key), None, length=niters)
            return sent_vec, errs[-1]

        return infer

    # -- driver (sent2vec.cpp:37-104) --------------------------------------
    def infer_sentences(self, lines: List[str], niters: int = 10,
                        tokenize_mode: str = "int", snapshot=None
                        ) -> List[Tuple[int, np.ndarray]]:
        """``snapshot``: a serve.TableSnapshot of the word table — when
        given, inference reads h/v and the key→slot map from that frozen
        published view instead of the live table, so it can run
        concurrently with a training loop (bounded staleness, never a
        torn mid-push state)."""
        wm = self.word_model
        if wm.vocab is None:
            raise RuntimeError(
                "word model has no vocab; train it in-process or load a "
                "dump via build_word_model_from_dump()")
        if self._infer is None:
            self._infer = self._build_infer()
        if snapshot is not None:
            h_table, v_table = (snapshot.tail_array("h"),
                                snapshot.tail_array("v"))
            slot_of_vocab = jnp.asarray(
                snapshot.lookup(wm.vocab.keys), jnp.int32)
        else:
            h_table, v_table = wm.table.state["h"], wm.table.state["v"]
            slot_of_vocab = wm._slot_of_vocab
        prob, alias = build_unigram_alias(wm.vocab.counts)
        # All-OOV lines are skipped entirely, like the reference skips
        # unparseable lines (sent2vec.cpp:71-74) — no garbage vectors.
        kept: List[Tuple[str, List[int]]] = []
        for ln in lines:
            t = [i for i in (wm.vocab.index_of(k)
                             for k in tokenize(ln, tokenize_mode))
                 if i is not None]
            if t:
                kept.append((ln, t))
        dropped = len(lines) - len(kept)
        if dropped:
            log.warning("sent2vec: skipped %d all-OOV sentence(s)", dropped)
        # Bounded dispatch pipeline: keep a window of batches in flight
        # and fetch the oldest as new ones are dispatched — a float(err)
        # + np.asarray(vecs) per batch is two blocking device round trips
        # that serialize what XLA
        # would otherwise pipeline, while an unbounded queue would hold
        # every batch's output on the device at once (O(input) HBM).
        MAX_IN_FLIGHT = 16
        queued = []
        out: List[Tuple[int, np.ndarray]] = []

        def drain_one():
            chunk, vecs, err = queued.pop(0)
            self.error.accu(float(err), len(chunk))
            vecs = np.asarray(vecs)
            for i, (ln, _) in enumerate(chunk):
                out.append((bkdr_hash(ln), vecs[i]))

        for start in range(0, len(kept), self.batchsize):
            chunk = kept[start:start + self.batchsize]
            S = self.batchsize          # pad tail: one compiled shape per L
            max_len = max(len(t) for _, t in chunk)
            L = 1 << (max_len - 1).bit_length()  # bucket to power of two
            vocab_pos = np.zeros((S, L), np.int32)
            mask = np.zeros((S, L), bool)
            for i, (_, t) in enumerate(chunk):
                vocab_pos[i, :len(t)] = t
                mask[i, :len(t)] = True
            slots = np.asarray(slot_of_vocab)[vocab_pos]
            self._key, sub = jax.random.split(self._key)
            vecs, err = self._infer(
                h_table, v_table,
                jnp.asarray(slots), jnp.asarray(mask),
                jnp.asarray(prob), jnp.asarray(alias),
                slot_of_vocab, jnp.asarray(vocab_pos),
                niters, sub)
            queued.append((chunk, vecs, err))
            while len(queued) >= MAX_IN_FLIGHT:
                drain_one()
        while queued:
            drain_one()
        log.info("sent2vec: %d sentences, error %.5f",
                 len(out), self.error.norm())
        if self.serve_publisher is not None and out:
            # publish the finished sentence vectors as a snapshot keyed
            # by sentence id — serve.query answers nearest-sentence
            # queries over it exactly like word neighbors
            self.serve_publisher.publish(
                {"sent": np.stack([v for _, v in out])},
                keys=np.array([s for s, _ in out], np.uint64),
                slots=np.arange(len(out), dtype=np.int64),
                meta={"query_field": "sent"})
        return out

    def write(self, results, path: str) -> None:
        """``sent_id\\tv0 v1 ...`` lines (sent2vec.cpp:82-86)."""
        with open(path, "w") as f:
            for sid, vec in results:
                f.write(f"{sid}\t" + " ".join(repr(float(x)) for x in vec)
                        + "\n")


def build_word_model_from_dump(dump_path: str, config: ConfigParser,
                               capacity_per_shard: int = 1 << 16
                               ) -> Word2Vec:
    """Load a word2vec text dump as the frozen word table, rebuilding the
    vocab bookkeeping sent2vec needs (counts default to 1 — the dump
    format, like the reference's, does not carry frequencies, so negative
    sampling over a loaded dump is uniform; train-in-process keeps true
    counts)."""
    model = Word2Vec(config=config, capacity_per_shard=capacity_per_shard)
    model.load(dump_path)
    keys = np.fromiter(model.table.key_index.keys(), np.uint64,
                       count=len(model.table.key_index))
    from swiftmpi_tpu.data.text import Vocab
    model.vocab = Vocab(keys, np.ones(len(keys), np.int64),
                        {int(k): i for i, k in enumerate(keys)})
    slots = model.table.key_index.lookup(keys)
    model._slot_of_vocab = jnp.asarray(slots, jnp.int32)
    return model
