"""Logistic-regression CLI, flag-compatible with the reference app.

Reference: ``/root/reference/src/apps/logistic/lr.cpp:413-509`` —
``-mode train|predict -config <conf> -dataset <file> -niters N
-param <weights> -output <file>``.  Launch is just ``python -m
swiftmpi_tpu.apps.lr_main ...``; there is no mpirun — the device mesh is
the cluster.
"""

from __future__ import annotations

import sys

import numpy as np

from swiftmpi_tpu.models.logistic import LogisticRegression
from swiftmpi_tpu.utils import CMDLine, global_config
from swiftmpi_tpu.utils.logger import get_logger
from swiftmpi_tpu.utils.xla_env import ensure_compile_cache

log = get_logger("apps.lr")


def main(argv=None) -> int:
    cmd = CMDLine(argv)
    cmd.registerParameter("help", "this screen")
    cmd.registerParameter("mode", "train/predict/eval (eval = the "
                          "reference tools/evaluate.py flow in-process: "
                          "threshold-at-0.5 error rate on a labeled set)")
    cmd.registerParameter("config", "path of config file")
    cmd.registerParameter("dataset", "path of dataset (libSVM format)")
    cmd.registerParameter("niters", "number of training iterations")
    cmd.registerParameter("param", "path of parameter file (predict/warm start)")
    cmd.registerParameter("output", "output path (predictions or weights)")
    if cmd.hasParameter("help") or not cmd.hasParameter("mode"):
        cmd.print_help()
        return 0

    ensure_compile_cache()
    if cmd.hasParameter("config"):
        global_config().load_conf(cmd.getValue("config")).parse()
    mode = cmd.getValue("mode")
    model = LogisticRegression()

    if mode == "train":
        niters = int(cmd.getValue("niters", "1"))
        losses = model.train(cmd.getValue("dataset"), niters=niters)
        log.info("final train error: %.6f", losses[-1])
        if cmd.hasParameter("output"):
            n = model.save(cmd.getValue("output"))
            log.info("wrote %d weights -> %s", n, cmd.getValue("output"))
        return 0

    if mode == "predict":
        if cmd.hasParameter("param"):
            model.load(cmd.getValue("param"))
        scores = model.predict(cmd.getValue("dataset"))
        out = cmd.getValue("output", "predict.txt")
        np.savetxt(out, scores, fmt="%.6f")
        log.info("wrote %d predictions -> %s", len(scores), out)
        return 0

    if mode == "eval":
        # reference: predictions file + labels -> tools/evaluate.py
        # (26-line offline error-rate script); here one mode does the
        # predict + threshold-at-0.5 compare in-process
        if not cmd.hasParameter("param"):
            # unlike predict (whose all-0.5 output file is visibly
            # degenerate), an untrained model's error rate is a
            # plausible-looking wrong scalar — refuse instead
            log.error("-mode eval requires -param <weights>")
            return 1
        model.load(cmd.getValue("param"))
        err = model.error_rate(cmd.getValue("dataset"))
        print(f"error rate: {err:.6f}")
        return 0

    log.error("unknown mode %r", mode)
    return 1


if __name__ == "__main__":
    sys.exit(main())
