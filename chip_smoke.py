#!/usr/bin/env python3
"""Chip smoke: the word2vec train path, end to end, on the accelerator.

The quickest proof that the system still starts on the chip.  One
process drives the same call sequence as ``swiftmpi_tpu/apps/w2v_main.py``
— conf file -> ``Cluster.initialize()`` -> native loader ->
``Word2Vec.build_from_vocab`` -> ``Word2Vec.train`` -> ``save`` — at the
repo's large-vocabulary CBOW shape: the reference demo.conf
hyperparameters over a 1,000,000-word vocabulary (1.3 M table rows x
100 dims, ``h``/``v`` + their AdaGrad planes ~ 2.1 GB f32 in HBM), on
however many chips it finds.  A second short run with ``[worker]
inner_steps`` > 1 compiles the fused ``lax.scan`` program.

``python3 chip_smoke.py`` demands the chip: it exits non-zero, with no
result line, when JAX finds no TPU.  On success the last stdout line is
``{"ok": true, "device": {...}}``.  The seconds it prints are smoke wall
times, not a benchmark.  The phases are plain functions with size
arguments so a CPU test can call them at toy size (tests/test_chip_smoke.py).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

VOCAB = 1_000_000
ZIPF_TOKENS = 1_600_000
# A whole epoch (4,160 steps of 625 centers) took ~130 s an iteration on
# one v5e chip (builder's run, PR 21): three of them plus the 110 s
# full-width save would spend half the 1200 s limit, so the train runs
# cover a truncated stream, as tests/_scale_child.py does.
TRAIN_TOKENS = 100_000       # 160 steps an iteration, three iterations
FUSED_TOKENS = 25_000        # 40 steps an iteration = 10 scan groups of 4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(ok, msg: str) -> None:
    if not ok:
        raise SystemExit(f"[chip_smoke] FAILED: {msg}")


# -- inputs -----------------------------------------------------------------

def write_corpus(path: str, vocab: int, zipf_tokens: int,
                 seed: int = 0) -> None:
    """Every word once plus a Zipf(1.3) tail, shuffled, 40 tokens a
    line (the tests/_scale_child.py generator; ~2.6 M tokens over 1 M
    distinct words at full size)."""
    rng = np.random.default_rng(seed)
    base = rng.permutation(vocab).astype(np.int64) + 1
    extra = (rng.zipf(1.3, size=zipf_tokens) % vocab) + 1
    toks = np.concatenate([base, extra])
    rng.shuffle(toks)
    with open(path, "w") as f:
        for start in range(0, len(toks), 40):
            f.write(" ".join(map(str, toks[start:start + 40])) + "\n")


def write_conf(path: str, transfer: str = "xla", inner_steps: int = 1,
               len_vec: int = 100, minibatch: int = 5000) -> None:
    """Reference demo.conf hyperparameters; ``[cluster]`` at its
    defaults apart from the transfer backend."""
    with open(path, "w") as f:
        f.write(f"[cluster]\ntransfer: {transfer}\n"
                f"[word2vec]\nlen_vec: {len_vec}\nwindow: 4\n"
                "negative: 20\nlearning_rate: 0.05\n"
                "[server]\ninitial_learning_rate: 0.7\n"
                f"[worker]\nminibatch: {minibatch}\n"
                f"inner_steps: {inner_steps}\n")


# -- the w2v_main call sequence -----------------------------------------------

def load_corpus(corpus_path: str, min_sentence_length: int):
    """(vocab, tokens, offsets, loader) through the native C++ loader,
    or the python tokenizer when the loader could not be built."""
    from swiftmpi_tpu.data import native
    if native.available():
        vocab, tokens, offsets = native.load_corpus_native(
            corpus_path, min_sentence_length=min_sentence_length)
        return vocab, tokens, offsets, "native"
    from swiftmpi_tpu.data.text import build_vocab, load_corpus as load_py
    sents = load_py(corpus_path, min_sentence_length=min_sentence_length)
    vocab = build_vocab(sents)
    tokens = np.fromiter((vocab.index[int(k)] for s in sents for k in s),
                         np.int32)
    offsets = np.concatenate(
        [[0], np.cumsum([len(s) for s in sents])]).astype(np.int64)
    return vocab, tokens, offsets, "python"


def truncate(tokens, offsets, max_tokens):
    """Whole sentences up to ``max_tokens`` tokens (None: everything)."""
    if max_tokens is None or max_tokens >= len(tokens):
        return tokens, offsets
    n_sent = int(np.searchsorted(offsets, max_tokens, side="right")) - 1
    return tokens[:int(offsets[n_sent])], offsets[:n_sent + 1]


def build_model(conf_path: str, vocab):
    """conf -> Cluster.initialize() -> table + sampler bring-up."""
    from swiftmpi_tpu.models.word2vec import Word2Vec
    from swiftmpi_tpu.utils import global_config, reset_global_config
    reset_global_config()
    global_config().load_conf(conf_path).parse()
    model = Word2Vec()
    model.build_from_vocab(vocab)
    return model


def make_batcher(model, vocab, tokens, offsets, loader: str):
    if loader == "native":
        from swiftmpi_tpu.data import native
        return native.PrefetchingCBOWBatcher(
            tokens, offsets, vocab, model.window, model.sample)
    from swiftmpi_tpu.data.text import CBOWBatcher
    sents = [vocab.keys[tokens[a:b]] for a, b in
             zip(offsets[:-1], offsets[1:])]
    return CBOWBatcher(sents, vocab, model.window, model.sample)


def train_fenced(model, batcher, niters: int):
    """``model.train`` with the wall clock stopped only after the table
    state is ready on the device.  Returns (losses, seconds)."""
    import jax
    t0 = time.perf_counter()
    losses = model.train(batcher=batcher, niters=niters)
    jax.block_until_ready(model.table.state)
    return losses, time.perf_counter() - t0


def steps_per_iter(model, n_tokens: int) -> int:
    batch = max(256, model.minibatch // (2 * model.window))
    return -(-n_tokens // batch)


# -- checks -----------------------------------------------------------------

def sample_rows(model, slots) -> dict:
    """Host copy of every field at ``slots`` (small D2H)."""
    import jax.numpy as jnp
    idx = jnp.asarray(slots, jnp.int32)
    return {f: np.asarray(a[idx]) for f, a in model.table.state.items()}


def pick_slots(model, vocab, tokens, n: int = 512, seed: int = 1):
    """(touched, untouched): slots of words the stream contains — each
    is some center's context, so its ``v`` row must move — and slots no
    key occupies, which nothing may write."""
    rng = np.random.default_rng(seed)
    words = np.unique(tokens)
    words = rng.choice(words, size=min(n, len(words)), replace=False)
    touched = model.table.key_index.lookup(vocab.keys[words])
    free = np.ones(model.table.capacity, bool)
    free[model.table.key_index.lookup(vocab.keys)] = False
    free = np.flatnonzero(free)
    require(len(free) > 0, "table has no unoccupied slot to sample")
    untouched = rng.choice(free, size=min(n, len(free)), replace=False)
    return touched, untouched


def check_rows(model, touched, untouched, before_t, before_u) -> None:
    after_t = sample_rows(model, touched)
    after_u = sample_rows(model, untouched)
    moved = np.any(after_t["v"] != before_t["v"], axis=1)
    require(moved.all(), f"{int((~moved).sum())}/{len(moved)} sampled "
            "context rows did not change in training")
    for f in after_u:
        require(np.array_equal(after_u[f], before_u[f]),
                f"unoccupied rows of field {f!r} changed")
    for f, a in after_t.items():
        require(np.isfinite(a).all(), f"non-finite values in field {f!r}")


def check_placement(model, platform: str) -> int:
    """Every field lives on ``platform`` devices, row-split evenly over
    the table axis.  Returns the table's bytes."""
    n_shards = int(model.cluster.mesh.shape[model.cluster.table_axis])
    total = 0
    for f, a in model.table.state.items():
        plats = {d.platform for d in a.sharding.device_set}
        require(plats == {platform},
                f"field {f!r} lives on {plats}, expected {platform}")
        rows = {s.data.shape[0] for s in a.addressable_shards}
        require(rows == {a.shape[0] // n_shards},
                f"field {f!r}: shard rows {rows}, expected "
                f"{a.shape[0]} / {n_shards}")
        total += a.nbytes
    return total


def save_and_reload(model, vocab) -> int:
    """``save`` then read the text back: one row per vocabulary word,
    values equal to the table's.  The text goes through a pipe straight
    into the reader, never onto the disk: at full width it is 2.4 GB,
    and the checkout the driver runs from had room for under 1 GB of it
    (PR 21's refused run)."""
    r, w = os.pipe()
    got = {}

    def read():
        try:
            got["rows"] = read_embeddings(f"/dev/fd/{r}", model.len_vec)
        finally:
            os.close(r)     # a reader that died must not leave save() blocked

    reader = threading.Thread(target=read)
    reader.start()
    try:
        n = model.save(f"/dev/fd/{w}")
    finally:
        os.close(w)         # end of file for the reader
        reader.join()
    require("rows" in got, "the embedding reader failed (traceback above)")
    keys, v_rows = got["rows"]
    require(n == len(vocab), f"saved {n} rows for {len(vocab)} words")
    require(len(keys) == len(vocab),
            f"read back {len(keys)} rows for {len(vocab)} words")
    require(np.array_equal(np.sort(keys), np.sort(vocab.keys)),
            "saved keys differ from the vocabulary")
    probe = np.arange(0, len(keys), max(len(keys) // 256, 1))
    want = sample_rows(model, model.table.key_index.lookup(keys[probe]))
    # the file carries the vector; the stored row may be wider (zeros)
    require(np.allclose(v_rows[probe], want["v"][:, :model.len_vec],
                        rtol=1e-6, atol=0),
            "saved v rows differ from the table")
    require(not want["v"][:, model.len_vec:].any(),
            "lanes beyond the vector are not zero")
    return n


def read_embeddings(path: str, len_vec: int):
    """(keys, v rows) of a ``key TAB v TAB h`` embedding file."""
    from swiftmpi_tpu.data import native
    if native.available():
        keys, (v, _h) = native.load_rows_native(path, (len_vec, len_vec))
        return keys, v
    keys, rows = [], []
    with open(path) as f:
        for line in f:
            k, v, _h = line.rstrip("\n").split("\t")
            keys.append(int(k))
            rows.append(np.array(v.split(), np.float32))
    return np.asarray(keys, np.uint64), np.stack(rows)


def peak_bytes(devices):
    """Per-device ``peak_bytes_in_use``, or None where the backend does
    not report memory statistics (XLA:CPU)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return [int(s["peak_bytes_in_use"]) for s in stats]


# -- the run ------------------------------------------------------------------

def run(out_dir: str, platform: str, vocab_size: int = VOCAB,
        zipf_tokens: int = ZIPF_TOKENS, max_tokens=TRAIN_TOKENS,
        fused_tokens: int = FUSED_TOKENS, transfer: str = "xla",
        len_vec: int = 100, minibatch: int = 5000) -> dict:
    """All phases at the given size; raises SystemExit on any miss."""
    import jax

    from swiftmpi_tpu.utils.xla_env import (compile_cache_entries,
                                            ensure_compile_cache)

    cache_dir = ensure_compile_cache()
    entries0 = compile_cache_entries(cache_dir)
    log(f"compile cache: {cache_dir} ({entries0} entries before)")
    os.makedirs(out_dir, exist_ok=True)
    devices = jax.devices()

    corpus = os.path.join(out_dir, "corpus.txt")
    t0 = time.perf_counter()
    write_corpus(corpus, vocab_size, zipf_tokens)
    conf = os.path.join(out_dir, "smoke.conf")
    write_conf(conf, transfer, 1, len_vec, minibatch)
    vocab, tokens, offsets, loader = load_corpus(corpus, 1)
    log(f"loader: {loader}; corpus {len(tokens)} tokens, vocab "
        f"{len(vocab)} words ({time.perf_counter() - t0:.1f}s)")
    require(len(vocab) >= 0.99 * vocab_size,
            f"vocab {len(vocab)} < 99% of {vocab_size}")

    # run 1: one step per dispatch (the w2v_step program)
    model = build_model(conf, vocab)
    table_bytes = check_placement(model, platform)
    log(f"table: capacity {model.table.capacity} rows x {model.len_vec}, "
        f"{len(model.table.state)} fields, {table_bytes / 1e9:.2f} GB, "
        f"mesh {dict(model.cluster.mesh.shape)}")
    toks, offs = truncate(tokens, offsets, max_tokens)
    batcher = make_batcher(model, vocab, toks, offs, loader)
    steps = steps_per_iter(model, len(toks))
    require(steps >= 20, f"only {steps} train steps per iteration")
    touched, untouched = pick_slots(model, vocab, toks)
    before_t = sample_rows(model, touched)
    before_u = sample_rows(model, untouched)
    first, first_s = train_fenced(model, batcher, 1)
    rest, rest_s = train_fenced(model, batcher, 2)
    losses = first + rest
    steady_iter_s = rest_s / 2
    log(f"train: {steps} steps/iteration over {len(toks)} tokens; first "
        f"call {first_s:.2f}s, steady iteration {steady_iter_s:.2f}s "
        f"({1e3 * steady_iter_s / steps:.2f} ms/step); compile+first-call "
        f"overhead {first_s - steady_iter_s:.2f}s")
    log("loss per iteration: " + " ".join(f"{x:.5f}" for x in losses))
    require(np.isfinite(losses).all(), f"non-finite loss {losses}")
    require(losses[-1] < losses[0],
            f"loss did not fall: {losses[0]:.5f} -> {losses[-1]:.5f}")
    check_rows(model, touched, untouched, before_t, before_u)
    check_placement(model, platform)
    t0 = time.perf_counter()
    n_saved = save_and_reload(model, vocab)
    log(f"saved and re-read {n_saved} embeddings through a pipe "
        f"({time.perf_counter() - t0:.1f}s)")

    # run 2: the fused lax.scan program ([worker] inner_steps > 1)
    del batcher, model
    gc.collect()
    write_conf(conf, transfer, 4, len_vec, minibatch)
    model = build_model(conf, vocab)
    toks, offs = truncate(tokens, offsets, fused_tokens)
    batcher = make_batcher(model, vocab, toks, offs, loader)
    fused_losses, fused_s = train_fenced(model, batcher, 2)
    require(4 in model._fused_cache,
            "inner_steps: 4 compiled no fused scan group")
    log(f"fused scan (inner_steps 4): {steps_per_iter(model, len(toks))} "
        f"steps/iteration, 2 iterations {fused_s:.2f}s, loss "
        + " ".join(f"{x:.5f}" for x in fused_losses))
    require(np.isfinite(fused_losses).all(),
            f"non-finite fused loss {fused_losses}")
    check_placement(model, platform)

    peaks = peak_bytes(devices)
    if peaks is not None:
        log("peak_bytes_in_use per device: "
            + " ".join(f"{p / 1e9:.2f}GB" for p in peaks))
    entries1 = compile_cache_entries(cache_dir)
    log(f"compile cache: {entries1} entries after (+{entries1 - entries0})")
    return {"losses": losses, "fused_losses": fused_losses,
            "table_bytes": table_bytes, "peak_bytes": peaks,
            "loader": loader,
            "first_call_s": first_s, "steady_iter_s": steady_iter_s}


def libtpu_version() -> str:
    from importlib import metadata
    for dist in ("libtpu", "libtpu-nightly"):
        try:
            return metadata.version(dist)
        except metadata.PackageNotFoundError:
            continue
    return "none"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--transfer", choices=["xla", "tpu"], default="xla",
                    help="[cluster] transfer backend (default: xla)")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    t0 = time.perf_counter()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"[chip_smoke] no TPU: jax.devices()[0] is "
                         f"{dev.platform!r} ({dev.device_kind})")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; jax {jax.__version__} jaxlib "
        f"{jaxlib.__version__} libtpu {libtpu_version()}")
    out = run(os.path.join(REPO, "runs", "chip_smoke"), "tpu",
              transfer=args.transfer)
    require(out["peak_bytes"] is not None,
            "the device reports no memory statistics")
    total_peak = sum(out["peak_bytes"])
    require(total_peak >= out["table_bytes"],
            f"peak HBM {total_peak} < table bytes {out['table_bytes']}: "
            "the table is not in device memory")
    log(f"smoke wall time {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
