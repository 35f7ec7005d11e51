"""GloVe CLI — same flag surface as the other app mains.

Beyond the reference's app set; exists to show the parameter-server
worker API generalizes (models/glove.py).  Flags follow the reference
convention (w2v.cpp:8-17): ``-config <conf> -data <corpus> -niters N
-output <path>``.  The output is the standard w + wt embedding sum in
the single-vector dump layout ``swiftmpi_tpu.apps.w2v_eval`` indexes
directly; ``-output-full`` additionally writes every field (both
families + AdaGrad sums) in the reference checkpoint format.
"""

from __future__ import annotations

import sys

from swiftmpi_tpu.data.text import load_corpus
from swiftmpi_tpu.models.glove import GloVe
from swiftmpi_tpu.utils import CMDLine, global_config
from swiftmpi_tpu.utils.logger import get_logger
from swiftmpi_tpu.utils.xla_env import ensure_compile_cache

log = get_logger("apps.glove")


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
    cmd.registerParameter("config", "path of config file ([glove] "
                          "section: len_vec/window/x_max/alpha/"
                          "learning_rate/minibatch)")
    cmd.registerParameter("data", "path of corpus (one sentence per "
                          "line)")
    cmd.registerParameter("niters", "number of training iterations")
    cmd.registerParameter("output", "path for the w+wt embedding dump")
    cmd.registerParameter("output-full", "path for the full-field "
                          "checkpoint (both families + AdaGrad sums)")
    if cmd.hasParameter("help") or not cmd.hasParameter("data"):
        cmd.print_help()
        return 0

    ensure_compile_cache()
    if cmd.hasParameter("config"):
        global_config().load_conf(cmd.getValue("config")).parse()
    model = GloVe()
    corpus = load_corpus(cmd.getValue("data"))
    niters = int(cmd.getValue("niters", "1"))
    losses = model.train(corpus, niters=niters)
    log.info("final loss: %.6f", losses[-1])
    if cmd.hasParameter("output"):
        n = model.save(cmd.getValue("output"))
        log.info("wrote %d embeddings -> %s", n, cmd.getValue("output"))
    if cmd.hasParameter("output-full"):
        n = model.save_full(cmd.getValue("output-full"))
        log.info("wrote %d full rows -> %s", n,
                 cmd.getValue("output-full"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
