"""word2vec CLI, flag-compatible with the reference mains.

Reference: ``/root/reference/src/apps/word2vec/w2v.cpp`` and
``w2v_local.cpp`` (identical CLIs: ``-config <conf> -data <corpus>
-niters N -output <path>``).  The two reference binaries differ in variant
(async/global with BKDR string keys vs sync with integer keys); here one
CLI takes ``-variant async|sync`` (default sync) which selects the
tokenizer and the local-steps staleness mode.
"""

from __future__ import annotations

import sys

from swiftmpi_tpu.data.text import load_corpus
from swiftmpi_tpu.models.word2vec import Word2Vec
from swiftmpi_tpu.utils import CMDLine, global_config
from swiftmpi_tpu.utils.logger import get_logger
from swiftmpi_tpu.utils.xla_env import ensure_compile_cache

log = get_logger("apps.w2v")


def main(argv=None) -> int:
    try:
        return _main(argv)
    finally:
        # clean teardown: a normal exit must not leave a misleading
        # reason="crash" flight-recorder dump behind (and must not
        # clobber a mid-run trigger dump at the same path)
        from swiftmpi_tpu import obs
        obs.uninstall_tracer()


def _main(argv=None) -> int:
    cmd = CMDLine(argv)
    cmd.registerParameter("help", "this screen")
    cmd.registerParameter("config", "path of config file")
    cmd.registerParameter("data", "path of dataset")
    cmd.registerParameter("niters", "number of iterations")
    cmd.registerParameter("output", "path to output the embeddings")
    cmd.registerParameter("variant", "sync (int keys) | async (hashed "
                          "keys, bounded staleness) | hogwild (hashed "
                          "keys, unsynchronized device replicas)")
    cmd.registerParameter("checkpoint",
                          "checkpoint path: save every iteration and "
                          "auto-resume if present (re-run the same "
                          "command after a crash to continue)")
    if cmd.hasParameter("help") or not cmd.hasParameter("data"):
        cmd.print_help()
        return 0

    ensure_compile_cache()
    if cmd.hasParameter("config"):
        global_config().load_conf(cmd.getValue("config")).parse()
    variant = cmd.getValue("variant", "sync")
    if variant not in ("sync", "async", "hogwild"):
        log.error("unknown -variant %r (expected sync|async|hogwild)",
                  variant)
        return 1
    if variant == "async":
        global_config().set("word2vec", "local_steps", 4)
    elif variant == "hogwild":
        global_config().set("word2vec", "async_mode", "hogwild")
    mode = "int" if variant == "sync" else "bkdr"

    model = Word2Vec()
    niters = int(cmd.getValue("niters", "1"))
    corpus, batcher = None, None
    from swiftmpi_tpu.data import native
    if native.available():
        # C++ fast path end to end: vocab, corpus mapping, and batch
        # assembly never touch the python tokenizer.
        vocab_c, tokens, offsets = native.load_corpus_native(
            cmd.getValue("data"), mode=mode,
            min_sentence_length=max(model.min_sentence_length, 1))
        batcher = native.PrefetchingCBOWBatcher(
            tokens, offsets, vocab_c, model.window, model.sample)
        log.info("using native C++ loader (prefetching)")
        model.build_from_vocab(vocab_c)
    else:
        corpus = load_corpus(cmd.getValue("data"), mode=mode,
                             min_sentence_length=model.min_sentence_length)
        model.build(corpus)
    if cmd.hasParameter("checkpoint"):
        from swiftmpi_tpu.io.resilience import train_with_resume
        losses = train_with_resume(
            model, corpus, niters=niters,
            checkpoint_path=cmd.getValue("checkpoint"),
            checkpoint_every=1, batcher=batcher)
        if not losses:
            log.info("checkpoint already at %d iters; nothing to train",
                     niters)
            if cmd.hasParameter("output"):
                n = model.save(cmd.getValue("output"))
                log.info("wrote %d embeddings -> %s", n,
                         cmd.getValue("output"))
            return 0
    else:
        losses = model.train(corpus, niters=niters, batcher=batcher)
    log.info("final error: %.5f", losses[-1])
    if cmd.hasParameter("output"):
        n = model.save(cmd.getValue("output"))
        log.info("wrote %d embeddings -> %s", n, cmd.getValue("output"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
