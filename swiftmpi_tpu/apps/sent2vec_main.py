"""sent2vec CLI, flag-compatible with the reference app.

Reference: ``/root/reference/src/apps/sent2vec/sent2vec.cpp:198-257`` —
``-config <conf> -data <sentences> -niters N -output <vecs out>
-wordvec <pre-trained word vectors>``.
"""

from __future__ import annotations

import sys

from swiftmpi_tpu.models.sent2vec import Sent2Vec, build_word_model_from_dump
from swiftmpi_tpu.utils import CMDLine, global_config
from swiftmpi_tpu.utils.logger import get_logger
from swiftmpi_tpu.utils.xla_env import ensure_compile_cache

log = get_logger("apps.sent2vec")


def main(argv=None) -> int:
    try:
        return _main(argv)
    finally:
        # normal exits leave no flight-recorder dump (obs/trace.py
        # clean-teardown contract)
        from swiftmpi_tpu import obs
        obs.uninstall_tracer()


def _main(argv=None) -> int:
    cmd = CMDLine(argv)
    cmd.registerParameter("help", "this screen")
    cmd.registerParameter("config", "path of config file")
    cmd.registerParameter("data", "path of dataset (one sentence per line)")
    cmd.registerParameter("niters", "gradient passes per sentence")
    cmd.registerParameter("output", "path to output sentence vectors")
    cmd.registerParameter("wordvec", "pre-trained word vectors (w2v dump)")
    if (cmd.hasParameter("help") or not cmd.hasParameter("data")
            or not cmd.hasParameter("wordvec")):
        cmd.print_help()
        return 0

    ensure_compile_cache()
    if cmd.hasParameter("config"):
        global_config().load_conf(cmd.getValue("config")).parse()
    word_model = build_word_model_from_dump(
        cmd.getValue("wordvec"), global_config())
    s2v = Sent2Vec(word_model)
    lines = [ln.rstrip("\n") for ln in open(cmd.getValue("data"))
             if ln.strip()]
    results = s2v.infer_sentences(lines,
                                  niters=int(cmd.getValue("niters", "10")))
    out = cmd.getValue("output", "sent_vecs.txt")
    s2v.write(results, out)
    log.info("wrote %d sentence vectors -> %s", len(results), out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
