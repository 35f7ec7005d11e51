"""The one traffic generator: a key stream from a mix's parameters and a seed.

A traffic mix is a data file (``benchmark/traffic/<mix>.json``); this module
is the only code that reads its ``keys`` / ``stream_tokens`` /
``sentence_tokens`` parameters.  A family turns the stream into its model's
inputs (word2vec: vocabulary + sentences; a later LR family: feature ids).

Keys are drawn by *rank*: rank 0 is the most likely key.  ``zipf`` draws by
inverse CDF from p(r) ~ 1 / (r + 1)^exponent over exactly ``n_keys`` ranks
(numpy's ``Generator.zipf`` has an unbounded tail and needs exponent > 1, so
it cannot make the natural-language exponent 1.0).  ``uniform`` draws ranks
uniformly.  With ``every_key_once`` each key also appears once, so all
``n_keys`` rows are live keys, as in ``chip_smoke.write_corpus``.
"""

from __future__ import annotations

import numpy as np


def rank_probabilities(keys: dict, n_keys: int) -> np.ndarray:
    """p(rank) of the mix's key law, float64, sums to 1."""
    dist = keys["distribution"]
    if dist == "zipf":
        w = 1.0 / np.arange(1, n_keys + 1, dtype=np.float64) \
            ** float(keys["exponent"])
    elif dist == "uniform":
        w = np.ones(n_keys, np.float64)
    else:
        raise ValueError(f"unknown key distribution {dist!r}")
    return w / w.sum()


def draw_ranks(rng: np.random.Generator, p: np.ndarray, n: int) -> np.ndarray:
    """``n`` i.i.d. ranks from ``p`` by inverse CDF."""
    cdf = np.cumsum(p)
    cdf[-1] = 1.0
    return np.searchsorted(cdf, rng.random(n), side="right").astype(np.int32)


def key_stream(traffic: dict, n_keys: int, seed: int):
    """(ranks, offsets): ``stream_tokens`` key ranks, shuffled, cut into
    sentences of ``sentence_tokens`` (the last may be shorter).  ``ranks``
    is int32, ``offsets`` int64 sentence boundaries.  Same seed, same
    stream."""
    n = int(traffic["stream_tokens"])
    keys = traffic["keys"]
    rng = np.random.default_rng([int(seed), 0x57E4])
    once = bool(keys.get("every_key_once"))
    if once and n < n_keys:
        raise ValueError(f"stream_tokens {n} < {n_keys} keys, but "
                         "every_key_once is set")
    drawn = draw_ranks(rng, rank_probabilities(keys, n_keys),
                       n - n_keys if once else n)
    ranks = np.concatenate([np.arange(n_keys, dtype=np.int32), drawn]) \
        if once else drawn
    rng.shuffle(ranks)
    step = int(traffic["sentence_tokens"])
    offsets = np.append(np.arange(0, n, step, dtype=np.int64), np.int64(n))
    return ranks, offsets
