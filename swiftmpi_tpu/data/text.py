"""Text corpus pipeline for word2vec: vocab, subsampling, CBOW batches.

Host-side equivalent of the reference gather/scan machinery:

* vocab + frequency build — the async variant's one global ``gather_keys``
  pass (`/root/reference/src/apps/word2vec/word2vec_global.h:385-444`).
* key derivation — both reference conventions: ``int`` (tokens are already
  integer ids, ``hash_fn2``/atoi, word2vec.h:206) and ``bkdr`` (string
  hash, word2vec_global.h:205-207).
* CBOW window extraction with the per-position random shrink ``b = rand %
  window`` giving effective half-window ``window - b`` (word2vec.h:555,
  567-576), subsampling by the reference keep-rule, and
  ``min_sentence_length`` filtering (word2vec.h:212-224).

Output batches are static-shape: ``centers (B,)``, ``contexts (B, 2W)`` +
mask, all as *vocab indices* (0..V-1); the model maps vocab index → table
slot on device.  Batch assembly is numpy; the C++ native loader is a
drop-in replacement for `iter_cbow_batches` (swiftmpi_tpu.data.native).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

from swiftmpi_tpu.ops.sampling import subsample_keep_prob
from swiftmpi_tpu.utils.hashing import bkdr_hash


@dataclass
class Vocab:
    keys: np.ndarray     # (V,) uint64 external key per vocab index
    counts: np.ndarray   # (V,) int64 corpus frequency
    index: Dict[int, int]  # uint64 key -> vocab index

    def __len__(self) -> int:
        return len(self.keys)

    @property
    def total_words(self) -> int:
        return int(self.counts.sum())

    def index_of(self, key: int):
        """Vocab index for a raw token key (negative ints wrap to uint64,
        matching storage), or None if OOV."""
        return self.index.get(int(key) & ((1 << 64) - 1))


def tokenize(line: str, mode: str = "int") -> List[int]:
    """Words -> integer keys: ``int`` = atoi (sync variant), ``bkdr`` =
    string hash (async variant)."""
    words = line.split()
    if mode == "int":
        out = []
        for w in words:
            try:
                out.append(int(w))
            except ValueError:
                out.append(bkdr_hash(w))
        return out
    if mode == "bkdr":
        return [bkdr_hash(w) for w in words]
    raise ValueError(f"unknown tokenize mode {mode!r}")


def build_vocab(sentences: Sequence[Sequence[int]],
                min_count: int = 1) -> Vocab:
    _M64 = (1 << 64) - 1
    counts: Dict[int, int] = {}
    for sent in sentences:
        for k in sent:
            k &= _M64  # normalize to uint64 (negative int tokens wrap,
            counts[k] = counts.get(k, 0) + 1  # matching the native loader)
    items = [(k, c) for k, c in counts.items() if c >= min_count]
    items.sort(key=lambda kc: (-kc[1], kc[0]))  # frequent-first, stable
    keys = np.array([k for k, _ in items], np.uint64)
    cnts = np.array([c for _, c in items], np.int64)
    return Vocab(keys, cnts, {int(k): i for i, (k, _) in enumerate(items)})


def load_corpus(path: str, mode: str = "int",
                min_sentence_length: int = 1,
                max_sentence_length: int = 1000) -> List[List[int]]:
    """Sentences as key lists; one line = one sentence, except single-line
    corpora (text8) which are chopped into ``max_sentence_length`` chunks
    (the reference reads text8 line-wise too — its LineFileReader returns
    the one giant line; chunking bounds the window scan the same way the
    reference's 1000-word sentence cap does in original word2vec)."""
    sentences = []
    with open(path) as f:
        for line in f:
            toks = tokenize(line, mode)
            for i in range(0, len(toks), max_sentence_length):
                chunk = toks[i:i + max_sentence_length]
                if len(chunk) >= min_sentence_length:
                    sentences.append(chunk)
    return sentences


@dataclass
class CBOWBatch:
    centers: np.ndarray   # (B,) int32 vocab indices
    contexts: np.ndarray  # (B, 2W) int32 vocab indices; 0 at padding
    ctx_mask: np.ndarray  # (B, 2W) bool
    n_words: int          # real (unpadded) center count

    def __len__(self) -> int:
        return len(self.centers)


#: span lengths are whole lane tiles of the chip
_SPAN_LANES = 128


def center_keep_mean(counts, keep_prob) -> float:
    """Share of stream positions that pass the center gate: ``k = sum
    f(w) keep(w)`` over the vocabulary, ``f`` a word's share of the
    stream.  1.0 without subsampling."""
    counts = np.asarray(counts, np.float64)
    total = counts.sum()
    if total <= 0:
        return 1.0
    return float(np.dot(counts / total, np.asarray(keep_prob, np.float64)))


def span_positions(batch_size: int, window: int,
                   keep_mean: float = 1.0) -> int:
    """Positions a span needs to hold ``batch_size`` centers when a
    position passes the center gate with probability ``keep_mean``: the
    mean ``B / k``, four standard deviations of the negative binomial
    (``sqrt(B (1 - k)) / k``), the ``2W`` a resumed left tail and the last
    window's right edge take, rounded up to whole lane tiles.  A batch
    that fills its span first closes short, which stays legal."""
    k = min(max(float(keep_mean), 1e-3), 1.0)
    need = (batch_size / k + 4.0 * np.sqrt(batch_size * (1.0 - k)) / k
            + 2 * window)
    return -(-int(np.ceil(need)) // _SPAN_LANES) * _SPAN_LANES


@dataclass
class StencilBatch:
    """Positional-stencil wire format: the batch is a *stream span* of
    ``S`` positions (``span_positions``: enough to hold ``B`` centers
    under the center gate) plus per-center positions into it, so the
    device pulls ``S`` rows instead of ``B * 2W`` context gathers.

    Expansion semantics (see :func:`stencil_to_cbow`): center row ``i``
    with ``p = center_pos[i]`` and ``h = half[i]`` has center token
    ``tokens[p]`` and contexts ``tokens[j]`` for ``j`` in
    ``[p-h, p+h]``, ``j != p``, ``0 <= j < S`` and
    ``sent_id[j] == sent_id[p]`` (sentence-boundary mask), in increasing
    ``j`` — identical content and order to the per-pair ``CBOWBatch``.
    """

    tokens: np.ndarray      # (S,) int32 span vocab indices; 0 at padding
    sent_id: np.ndarray     # (S,) int32 batch-local sentence id; -1 pad
    center_pos: np.ndarray  # (B,) int32 span index per center; -1 pad
    half: np.ndarray        # (B,) int32 effective half-window; 0 pad
    n_words: int            # real (unpadded) center count
    #: the four fields in one int32 buffer, ``[tokens | sent_id |
    #: center_pos | half]``, where the batcher laid them out so (the
    #: fields are then views of it): what goes to the device, in one put
    packed: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return len(self.center_pos)

    @property
    def span(self) -> int:
        return len(self.tokens)

    def pack(self) -> np.ndarray:
        """``(2S + 2B,)`` int32: ``packed``, or the fields joined."""
        if self.packed is not None:
            return self.packed
        return np.concatenate([
            np.asarray(f, np.int32) for f in
            (self.tokens, self.sent_id, self.center_pos, self.half)])


def unpack_span(packed, centers: int):
    """``(tokens, sent_id, center_pos, half)`` of a packed span batch of
    ``centers`` centers (`StencilBatch.pack`); works on host and traced
    arrays alike."""
    S = (packed.shape[-1] - 2 * centers) // 2
    return (packed[..., :S], packed[..., S:2 * S],
            packed[..., 2 * S:2 * S + centers],
            packed[..., 2 * S + centers:])


def stencil_to_cbow(batch: StencilBatch, window: int) -> CBOWBatch:
    """Host-side expansion of a stencil batch to per-pair rows — the
    parity anchor: with the same seed, the expanded stream must equal
    the per-pair batcher's stream element for element."""
    W = int(window)
    B = len(batch.center_pos)
    S = batch.span
    centers = np.zeros(B, np.int32)
    ctxs = np.zeros((B, 2 * W), np.int32)
    mask = np.zeros((B, 2 * W), bool)
    for i in range(batch.n_words):
        p = int(batch.center_pos[i])
        h = int(batch.half[i])
        sid = int(batch.sent_id[p])
        js = [j for j in range(p - h, p + h + 1)
              if j != p and 0 <= j < S and batch.sent_id[j] == sid]
        ctx = batch.tokens[js]
        centers[i] = batch.tokens[p]
        ctxs[i, :len(ctx)] = ctx
        mask[i, :len(ctx)] = True
    return CBOWBatch(centers, ctxs, mask, batch.n_words)


class CBOWBatcher:
    """Streams fixed-size CBOW batches over a corpus."""

    def __init__(self, sentences: Sequence[Sequence[int]], vocab: Vocab,
                 window: int, sample: float = -1.0, seed: int = 2008):
        self.vocab = vocab
        self.window = int(window)
        self.sample = float(sample)
        self.rng = np.random.default_rng(seed)
        self.keep_prob = subsample_keep_prob(vocab.counts, sample)
        self.keep_mean = center_keep_mean(vocab.counts, self.keep_prob)
        # pre-map sentences to vocab indices, dropping OOV
        self._sents: List[np.ndarray] = []
        for sent in sentences:
            idx = [i for i in (vocab.index_of(k) for k in sent)
                   if i is not None]
            if idx:
                self._sents.append(np.asarray(idx, np.int32))

    def epoch(self, batch_size: int) -> Iterator[CBOWBatch]:
        """One pass over the corpus in a fresh random sentence order.

        Subsampling follows the reference exactly: ``to_sample`` gates only
        the *center* position (word2vec.h:561-562 ``continue``); dropped
        words still appear in their neighbors' context windows.
        """
        W = self.window
        centers: List[int] = []
        ctxs: List[np.ndarray] = []
        masks: List[np.ndarray] = []

        def flush(n_real):
            c = np.asarray(centers[:batch_size], np.int32)
            x = np.stack(ctxs[:batch_size])
            m = np.stack(masks[:batch_size])
            del centers[:batch_size], ctxs[:batch_size], masks[:batch_size]
            return CBOWBatch(c, x, m, n_real)

        for si in self.rng.permutation(len(self._sents)):
            sent = self._sents[si]
            L = len(sent)
            # per-position random shrink b in [0, W)  (word2vec.h:555)
            bs = self.rng.integers(0, W, size=L)
            if self.sample >= 0:
                center_keep = (self.rng.random(L)
                               < self.keep_prob[sent])
            else:
                center_keep = np.ones(L, bool)
            for pos in range(L):
                if not center_keep[pos]:
                    continue
                half = W - int(bs[pos])
                lo, hi = max(0, pos - half), min(L, pos + half + 1)
                ctx = np.concatenate([sent[lo:pos], sent[pos + 1:hi]])
                if len(ctx) == 0:
                    continue
                row = np.zeros(2 * W, np.int32)
                row[:len(ctx)] = ctx
                m = np.zeros(2 * W, bool)
                m[:len(ctx)] = True
                centers.append(int(sent[pos]))
                ctxs.append(row)
                masks.append(m)
                if len(centers) == batch_size:
                    yield flush(batch_size)
        if centers:
            n_real = len(centers)
            # pad tail to the static batch shape with masked rows
            while len(centers) < batch_size:
                centers.append(0)
                ctxs.append(np.zeros(2 * W, np.int32))
                masks.append(np.zeros(2 * W, bool))
            yield flush(n_real)

    def epoch_stencil(self, batch_size: int) -> Iterator[StencilBatch]:
        """One pass emitting :class:`StencilBatch` stream spans.

        Consumes the rng in *exactly* the order :meth:`epoch` does
        (permutation, then per-sentence shrink array + keep array), so
        the expanded pair stream for a given seed is identical to the
        per-pair epoch — the CPU parity tests pin this.

        Invariants (by construction, not by dedup):
        * span capacity is fixed at ``S = span_positions(batch_size, W,
          keep_mean)`` — what holds a full batch of centers under the
          center gate (``batch_size + 2W`` rounded up without one);
        * every admitted center's full (sentence-clipped) window is
          resident in the span, so expansion never loses a context;
        * a sentence split across batches replays its last ``W`` tokens
          into the new span so left contexts survive the split.
        """
        W = self.window
        S = span_positions(batch_size, W, self.keep_mean)
        tokens = np.zeros(S, np.int32)
        sids = np.full(S, -1, np.int32)
        cpos = np.full(batch_size, -1, np.int32)
        halves = np.zeros(batch_size, np.int32)
        fill = 0   # span rows used
        nc = 0     # centers admitted
        ns = 0     # batch-local sentence counter

        def flush():
            nonlocal tokens, sids, cpos, halves, fill, nc, ns
            out = StencilBatch(tokens, sids, cpos, halves, nc)
            tokens = np.zeros(S, np.int32)
            sids = np.full(S, -1, np.int32)
            cpos = np.full(batch_size, -1, np.int32)
            halves = np.zeros(batch_size, np.int32)
            fill = nc = ns = 0
            return out

        for si in self.rng.permutation(len(self._sents)):
            sent = self._sents[si]
            L = len(sent)
            bs = self.rng.integers(0, W, size=L)
            if self.sample >= 0:
                center_keep = (self.rng.random(L)
                               < self.keep_prob[sent])
            else:
                center_keep = np.ones(L, bool)
            sid = ns
            ns += 1
            p0 = 0       # first sentence position resident in the span
            base = fill  # span index of sentence position p0
            have = 0     # sentence positions [p0, p0+have) are appended
            p = 0
            while p < L:
                half = W - int(bs[p])
                left = min(half, p)
                right = min(half, L - 1 - p)
                if not center_keep[p] or left + right == 0:
                    p += 1
                    continue
                if have == 0:
                    # nothing resident yet: skip any keep-dropped prefix
                    # no future window can reach (all reach >= p - W)
                    p0 = max(p0, p - W)
                end = p + right         # last sentence position needed
                if nc == batch_size or base + (end - p0) >= S:
                    yield flush()
                    # resume mid-sentence: replay the left tail so
                    # upcoming centers keep their left context
                    p0 = max(0, p - W)
                    base = 0
                    n = p - p0
                    tokens[:n] = sent[p0:p]
                    sids[:n] = 0
                    fill = have = n
                    sid, ns = 0, 1
                    continue            # re-admit p in the fresh span
                # append (contiguously) through the window's right edge
                if end - p0 >= have:
                    n_new = end - p0 + 1 - have
                    tokens[fill:fill + n_new] = sent[p0 + have:end + 1]
                    sids[fill:fill + n_new] = sid
                    fill += n_new
                    have += n_new
                cpos[nc] = base + (p - p0)
                halves[nc] = half
                nc += 1
                p += 1
        if nc:
            yield flush()

    def epoch_prefetch(self, batch_size: int, depth: int = 4
                       ) -> Iterator[CBOWBatch]:
        """:meth:`epoch` through a background producer thread
        (io/pipeline.py): rendering runs ``depth`` batches ahead while
        the consumer computes.  Batch order and rng consumption are
        identical to the synchronous epoch — the producer just runs
        the same generator earlier."""
        from swiftmpi_tpu.io.pipeline import PrefetchIterator
        return PrefetchIterator(self.epoch(batch_size), depth=depth,
                                name="cbow-epoch-prefetch")

    def epoch_stencil_prefetch(self, batch_size: int, depth: int = 4
                               ) -> Iterator[StencilBatch]:
        """:meth:`epoch_stencil` through the same background producer
        (identical wire format and order)."""
        from swiftmpi_tpu.io.pipeline import PrefetchIterator
        return PrefetchIterator(self.epoch_stencil(batch_size),
                                depth=depth,
                                name="cbow-stencil-prefetch")


def synthetic_corpus(n_sentences: int, vocab_size: int, length: int = 20,
                     seed: int = 0, zipf: float = 1.2) -> List[List[int]]:
    """Zipf-distributed token streams with local correlation (neighbors
    share a topic), so embeddings have signal to learn."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    p = ranks ** (-zipf)
    p /= p.sum()
    out = []
    for _ in range(n_sentences):
        topic = rng.integers(0, 5)
        base = rng.choice(vocab_size, size=length, p=p)
        # topic words interleaved -> co-occurrence structure
        base[::3] = (topic * 7 + base[::3] // 5) % vocab_size
        out.append([int(x) + 1 for x in base])  # keys are 1-based ints
    return out


def synthetic_corpus_bulk(n_sentences: int, vocab_size: int,
                          length: int = 1000, seed: int = 0,
                          zipf: float = 1.2) -> np.ndarray:
    """Bulk rendering of :func:`synthetic_corpus`'s distribution for
    enwiki-scale corpora (BASELINE config #3: 100M tokens / few-hundred-K
    vocab): one CDF + vectorized ``searchsorted`` draws instead of a
    per-sentence ``rng.choice(p=...)`` (whose per-call CDF rebuild is
    O(V) — hours at 100K x 1000).  Returns an (n_sentences, length)
    int32 array of 1-based keys with the same Zipf marginal and
    per-sentence topic interleave as the list generator."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** (-zipf))
    cdf /= cdf[-1]
    out = np.empty((n_sentences, length), np.int32)
    # row chunks bound the float64 draw + int64 searchsorted transients
    # to ~tens of MB (one full 100Kx1000 draw would transiently hold
    # ~2GB — review finding)
    chunk = max(1, 2_000_000 // max(length, 1))
    for i in range(0, n_sentences, chunk):
        n = min(chunk, n_sentences - i)
        base = np.searchsorted(
            cdf, rng.random((n, length)), side="right")
        topics = rng.integers(0, 5, size=(n, 1))
        base[:, ::3] = (topics * 7 + base[:, ::3] // 5) % vocab_size
        out[i:i + n] = base + 1                  # keys are 1-based ints
    return out
