"""Device-side ops: sampling, activation kernels."""

from swiftmpi_tpu.ops.sampling import (build_unigram_alias, sample_alias,
                                       subsample_keep_prob)
from swiftmpi_tpu.ops.sigmoid import MAX_EXP, sigmoid_clipped

__all__ = ["build_unigram_alias", "sample_alias", "subsample_keep_prob",
           "MAX_EXP", "sigmoid_clipped"]
