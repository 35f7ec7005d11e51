"""ctypes binding to the native C++ data loader (native/loader.cpp).

Drop-in fast path for the host input pipeline: vocab build, corpus
mapping, and CBOW batch assembly run in C++ (the reference's own host-side
machinery is C++ — LineFileReader/split/gather_keys).  Falls back to the
pure-Python pipeline (data/text.py) when the shared library cannot be
built; call ``available()`` to check.

The .so is built on demand with g++ from the repo's ``native/`` directory
and cached next to the source (gitignored: every checkout compiles its
own from ``loader.cpp`` + ``io.cpp``).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Iterator, List, Optional, Sequence

import numpy as np

from swiftmpi_tpu.data.text import (CBOWBatch, StencilBatch, Vocab,
                                     center_keep_mean, span_positions,
                                     unpack_span)
from swiftmpi_tpu.utils.logger import get_logger

log = get_logger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libsmtpu_loader.so")

_lib = None
_lib_lock = threading.Lock()
_build_failed = False


def _load_lib():
    global _lib, _build_failed
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        srcs = [os.path.join(_NATIVE_DIR, f)
                for f in ("loader.cpp", "io.cpp")]
        stale = (not os.path.exists(_SO_PATH)
                 or any(os.path.exists(s)
                        and os.path.getmtime(s) > os.path.getmtime(_SO_PATH)
                        for s in srcs))
        if stale:
            srcs = [s for s in srcs if os.path.exists(s)]
            if not srcs:
                _build_failed = True
                return None
            try:
                subprocess.run(
                    ["g++", "-O3", "-std=c++17", "-Wall", "-shared",
                     "-fPIC", *srcs, "-o", _SO_PATH],
                    check=True, capture_output=True, text=True,
                    timeout=120)
            except (subprocess.SubprocessError, FileNotFoundError) as e:
                log.warning("native loader build failed (%s); using "
                            "python pipeline\n%s", e,
                            getattr(e, "stderr", None) or "")
                _build_failed = True
                return None
        lib = ctypes.CDLL(_SO_PATH, use_errno=True)
        c = ctypes
        lib.smtpu_vocab_build.restype = c.c_void_p
        lib.smtpu_vocab_build.argtypes = [c.c_char_p, c.c_int, c.c_int64,
                                          c.c_int64, c.c_int64]
        lib.smtpu_vocab_size.restype = c.c_int64
        lib.smtpu_vocab_size.argtypes = [c.c_void_p]
        lib.smtpu_vocab_copy.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p]
        lib.smtpu_vocab_free.argtypes = [c.c_void_p]
        lib.smtpu_corpus_map.restype = c.c_void_p
        lib.smtpu_corpus_map.argtypes = [c.c_char_p, c.c_int, c.c_void_p,
                                         c.c_int64, c.c_int64]
        lib.smtpu_corpus_n_sentences.restype = c.c_int64
        lib.smtpu_corpus_n_sentences.argtypes = [c.c_void_p]
        lib.smtpu_corpus_n_tokens.restype = c.c_int64
        lib.smtpu_corpus_n_tokens.argtypes = [c.c_void_p]
        lib.smtpu_corpus_copy.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p]
        lib.smtpu_corpus_free.argtypes = [c.c_void_p]
        lib.smtpu_batcher_new.restype = c.c_void_p
        lib.smtpu_batcher_new.argtypes = [c.c_void_p, c.c_void_p, c.c_int64,
                                          c.c_int, c.c_void_p, c.c_uint64]
        lib.smtpu_batcher_reset.argtypes = [c.c_void_p, c.c_uint64]
        lib.smtpu_batcher_next.restype = c.c_int64
        lib.smtpu_batcher_next.argtypes = [c.c_void_p, c.c_int64, c.c_void_p,
                                           c.c_void_p, c.c_void_p]
        lib.smtpu_batcher_next_span.restype = c.c_int64
        lib.smtpu_batcher_next_span.argtypes = [
            c.c_void_p, c.c_int64, c.c_int64, c.c_void_p, c.c_void_p,
            c.c_void_p, c.c_void_p]
        lib.smtpu_batcher_free.argtypes = [c.c_void_p]
        lib.smtpu_prefetcher_new.restype = c.c_void_p
        lib.smtpu_prefetcher_new.argtypes = [c.c_void_p, c.c_int64,
                                             c.c_int64, c.c_uint64]
        lib.smtpu_prefetcher_next.restype = c.c_int64
        lib.smtpu_prefetcher_next.argtypes = [c.c_void_p, c.c_void_p,
                                              c.c_void_p, c.c_void_p]
        lib.smtpu_prefetcher_free.argtypes = [c.c_void_p]
        lib.smtpu_libsvm_parse.restype = c.c_void_p
        lib.smtpu_libsvm_parse.argtypes = [c.c_char_p]
        lib.smtpu_libsvm_n_rows.restype = c.c_int64
        lib.smtpu_libsvm_n_rows.argtypes = [c.c_void_p]
        lib.smtpu_libsvm_nnz.restype = c.c_int64
        lib.smtpu_libsvm_nnz.argtypes = [c.c_void_p]
        lib.smtpu_libsvm_n_bad.restype = c.c_int64
        lib.smtpu_libsvm_n_bad.argtypes = [c.c_void_p]
        lib.smtpu_libsvm_copy.argtypes = [c.c_void_p] + [c.c_void_p] * 4
        lib.smtpu_libsvm_free.argtypes = [c.c_void_p]
        lib.smtpu_dump_rows.restype = c.c_int64
        lib.smtpu_dump_rows.argtypes = [c.c_char_p, c.c_void_p, c.c_int64,
                                        c.c_int64, c.c_void_p, c.c_void_p]
        lib.smtpu_load_rows.restype = c.c_void_p
        lib.smtpu_load_rows.argtypes = [c.c_char_p, c.c_int64, c.c_void_p]
        lib.smtpu_text_n_rows.restype = c.c_int64
        lib.smtpu_text_n_rows.argtypes = [c.c_void_p]
        lib.smtpu_text_copy.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p]
        lib.smtpu_text_free.argtypes = [c.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    return _load_lib() is not None


_MODE = {"int": 0, "bkdr": 1}


def load_corpus_native(path: str, mode: str = "int", min_count: int = 1,
                       min_sentence_length: int = 1,
                       max_sentence_length: int = 1000):
    """One C++ pass for vocab + one for corpus mapping.

    Returns (vocab, tokens, offsets): ``tokens`` int32 vocab indices
    flattened, ``offsets`` int64 sentence boundaries.
    """
    lib = _load_lib()
    if lib is None:
        raise RuntimeError("native loader unavailable")
    vp = lib.smtpu_vocab_build(path.encode(), _MODE[mode], min_count,
                               min_sentence_length, max_sentence_length)
    if not vp:
        raise FileNotFoundError(path)
    try:
        V = lib.smtpu_vocab_size(vp)
        keys = np.empty(V, np.uint64)
        counts = np.empty(V, np.int64)
        lib.smtpu_vocab_copy(vp, keys.ctypes.data, counts.ctypes.data)
        vocab = Vocab(keys, counts,
                      {int(k): i for i, k in enumerate(keys)})
        cp = lib.smtpu_corpus_map(path.encode(), _MODE[mode], vp,
                                  min_sentence_length, max_sentence_length)
        if not cp:
            raise FileNotFoundError(path)
        try:
            n_sent = lib.smtpu_corpus_n_sentences(cp)
            n_tok = lib.smtpu_corpus_n_tokens(cp)
            tokens = np.empty(n_tok, np.int32)
            offsets = np.empty(n_sent + 1, np.int64)
            lib.smtpu_corpus_copy(cp, tokens.ctypes.data,
                                  offsets.ctypes.data)
        finally:
            lib.smtpu_corpus_free(cp)
    finally:
        lib.smtpu_vocab_free(vp)
    return vocab, tokens, offsets


class NativeCBOWBatcher:
    """C++-backed drop-in for ``CBOWBatcher`` (same batch contract)."""

    def __init__(self, tokens: np.ndarray, offsets: np.ndarray, vocab: Vocab,
                 window: int, sample: float = -1.0, seed: int = 2008):
        from swiftmpi_tpu.ops.sampling import subsample_keep_prob
        lib = _load_lib()
        if lib is None:
            raise RuntimeError("native loader unavailable")
        self._lib = lib
        self.window = int(window)
        self.vocab = vocab
        # keep buffer refs alive: the batcher borrows these arrays
        self._tokens = np.ascontiguousarray(tokens, np.int32)
        self._offsets = np.ascontiguousarray(offsets, np.int64)
        if sample >= 0:
            self._keep = np.ascontiguousarray(
                subsample_keep_prob(vocab.counts, sample), np.float32)
            keep_ptr = self._keep.ctypes.data
            self.keep_mean = center_keep_mean(vocab.counts, self._keep)
        else:
            self._keep = None
            keep_ptr = None
            self.keep_mean = 1.0
        self._seed = seed
        self._epoch_i = 0
        self._h = lib.smtpu_batcher_new(
            self._tokens.ctypes.data, self._offsets.ctypes.data,
            len(self._offsets) - 1, self.window, keep_ptr, seed)

    def _drain(self, batch_size: int, next_fn) -> Iterator[CBOWBatch]:
        """Shared batch-yield loop: ``next_fn(centers, contexts, mask)``
        fills one batch and returns n examples (0 = epoch done)."""
        W2 = 2 * self.window
        while True:
            centers = np.zeros(batch_size, np.int32)
            contexts = np.zeros((batch_size, W2), np.int32)
            mask = np.zeros((batch_size, W2), np.uint8)
            n = next_fn(centers.ctypes.data, contexts.ctypes.data,
                        mask.ctypes.data)
            if n == 0:
                return
            yield CBOWBatch(centers, contexts, mask.astype(bool), int(n))
            if n < batch_size:
                return

    def epoch(self, batch_size: int) -> Iterator[CBOWBatch]:
        lib = self._lib
        self._epoch_i += 1
        lib.smtpu_batcher_reset(self._h, self._seed + self._epoch_i)
        yield from self._drain(
            batch_size,
            lambda c, x, m: lib.smtpu_batcher_next(
                self._h, batch_size, c, x, m))

    def epoch_stencil(self, batch_size: int) -> Iterator[StencilBatch]:
        """Stream-span epoch (same wire format as
        ``CBOWBatcher.epoch_stencil``): spans of ``span_positions``
        stream positions — what holds ``batch_size`` centers under this
        batcher's own center gate — with per-center positions, assembled
        in C++ into one buffer (``StencilBatch.packed``)."""
        lib = self._lib
        S = span_positions(batch_size, self.window, self.keep_mean)
        self._epoch_i += 1
        lib.smtpu_batcher_reset(self._h, self._seed + self._epoch_i)
        while True:
            packed = np.empty(2 * S + 2 * batch_size, np.int32)
            tokens, sids, cpos, half = unpack_span(packed, batch_size)
            n = lib.smtpu_batcher_next_span(
                self._h, batch_size, S, tokens.ctypes.data,
                sids.ctypes.data, cpos.ctypes.data, half.ctypes.data)
            if n == 0:
                return
            yield StencilBatch(tokens, sids, cpos, half, int(n), packed)

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.smtpu_batcher_free(self._h)
                self._h = None
        except Exception:
            pass


class PrefetchingCBOWBatcher(NativeCBOWBatcher):
    """NativeCBOWBatcher whose epochs run through the C++ prefetch
    executor: a producer thread assembles batches into a bounded queue
    while the device computes (the reference AsynExec/queue_with_capacity
    machinery recast as input-pipeline overlap — loader.cpp)."""

    def __init__(self, *args, depth: int = 4, **kwargs):
        super().__init__(*args, **kwargs)
        self.depth = int(depth)

    def epoch(self, batch_size: int) -> Iterator[CBOWBatch]:
        lib = self._lib
        self._epoch_i += 1
        p = lib.smtpu_prefetcher_new(self._h, batch_size, self.depth,
                                     self._seed + self._epoch_i)
        try:
            yield from self._drain(
                batch_size,
                lambda c, x, m: lib.smtpu_prefetcher_next(p, c, x, m))
        finally:
            lib.smtpu_prefetcher_free(p)

    def epoch_stencil(self, batch_size: int) -> Iterator[StencilBatch]:
        """The C++ prefetch executor covers only the per-pair wire
        format; the stencil epoch gets the same overlap through the
        Python-thread pipeline (io/pipeline.py) over the synchronous
        native iterator (the C++ call releases the interpreter lock) —
        wire format and batch order unchanged.  It is this batcher's own
        read-ahead, as the C++ executor is ``epoch()``'s, not the train
        loop's ``[worker] pipeline``: it tells no phase of its own, the
        loop's ``input_wait`` covers the wait for it."""
        from swiftmpi_tpu.io.pipeline import PrefetchIterator
        return PrefetchIterator(super().epoch_stencil(batch_size),
                                depth=self.depth,
                                name="native-stencil-prefetch",
                                observed=False)


# ---- libSVM (io.cpp) ------------------------------------------------------

def parse_libsvm_native(path: str):
    """Whole-file CSR parse: (labels (N,), offsets (N+1,), feat_ids (nnz,),
    feat_vals (nnz,)).  Labels are already mapped to {0,1}."""
    lib = _load_lib()
    if lib is None:
        raise RuntimeError("native loader unavailable")
    h = lib.smtpu_libsvm_parse(path.encode())
    if not h:
        raise FileNotFoundError(path)
    try:
        n_bad = lib.smtpu_libsvm_n_bad(h)
        if n_bad:
            raise ValueError(
                f"{path}: {n_bad} malformed libSVM line(s) "
                "(bad label or feature token)")
        n = lib.smtpu_libsvm_n_rows(h)
        nnz = lib.smtpu_libsvm_nnz(h)
        labels = np.empty(n, np.float32)
        offsets = np.empty(n + 1, np.int64)
        ids = np.empty(nnz, np.uint64)
        vals = np.empty(nnz, np.float32)
        lib.smtpu_libsvm_copy(h, labels.ctypes.data, offsets.ctypes.data,
                              ids.ctypes.data, vals.ctypes.data)
    finally:
        lib.smtpu_libsvm_free(h)
    return labels, offsets, ids, vals


# ---- text checkpoints (io.cpp) --------------------------------------------

def dump_rows_native(path: str, keys: np.ndarray, fields) -> int:
    """Write ``key\\tfield0\\tfield1...`` lines; ``fields`` is an ordered
    list of (n, d) float32 arrays.  Returns rows written."""
    lib = _load_lib()
    if lib is None:
        raise RuntimeError("native loader unavailable")
    keys = np.ascontiguousarray(keys, np.uint64)
    if len(keys) == 0:  # empty table: empty file, like the python writer
        open(path, "w").close()
        return 0
    arrs = [np.ascontiguousarray(a, np.float32).reshape(len(keys), -1)
            for a in fields]
    dims = np.asarray([a.shape[1] for a in arrs], np.int64)
    ptrs = (ctypes.c_void_p * len(arrs))(
        *[a.ctypes.data for a in arrs])
    n = lib.smtpu_dump_rows(path.encode(), keys.ctypes.data, len(keys),
                            len(arrs), ptrs, dims.ctypes.data)
    if n < 0:
        err = ctypes.get_errno()
        raise OSError(err, os.strerror(err), path)
    return int(n)


def load_rows_native(path: str, dims):
    """Read ``key\\tfield...`` lines where field j has ``dims[j]`` floats.
    Returns (keys (N,), [(N, dims[j]) float32 arrays])."""
    lib = _load_lib()
    if lib is None:
        raise RuntimeError("native loader unavailable")
    dims = np.asarray(dims, np.int64)
    h = lib.smtpu_load_rows(path.encode(), len(dims), dims.ctypes.data)
    if not h:
        raise FileNotFoundError(path)
    try:
        n = lib.smtpu_text_n_rows(h)
        keys = np.empty(n, np.uint64)
        arrs = [np.empty((n, int(d)), np.float32) for d in dims]
        ptrs = (ctypes.c_void_p * len(arrs))(
            *[a.ctypes.data for a in arrs])
        lib.smtpu_text_copy(h, keys.ctypes.data, ptrs)
    finally:
        lib.smtpu_text_free(h)
    return keys, arrs
