"""The traffic generator: deterministic in the seed, and the law it claims."""

import numpy as np
import pytest

from benchmark.lib import traffic

MIX = {"keys": {"distribution": "zipf", "exponent": 1.0,
                "every_key_once": True},
       "stream_tokens": 400_000, "sentence_tokens": 40}


def test_same_seed_same_stream_other_seed_other_stream():
    a, oa = traffic.key_stream(MIX, 20_000, 7)
    b, ob = traffic.key_stream(MIX, 20_000, 7)
    c, _ = traffic.key_stream(MIX, 20_000, 8)
    assert np.array_equal(a, b) and np.array_equal(oa, ob)
    assert not np.array_equal(a, c)
    assert a.dtype == np.int32 and oa.dtype == np.int64


def test_every_key_once_and_sentences():
    ranks, offsets = traffic.key_stream(MIX, 20_000, 1)
    assert len(ranks) == 400_000
    assert np.bincount(ranks, minlength=20_000).min() >= 1
    assert offsets[0] == 0 and offsets[-1] == len(ranks)
    assert set(np.diff(offsets)) == {40}
    odd = dict(MIX, stream_tokens=400_010)
    assert np.diff(traffic.key_stream(odd, 20_000, 1)[1])[-1] == 10


def test_zipf_shaped():
    """Rank-frequency follows 1/r: the head counts match n * p(r) within
    sampling error, and the log-log slope over the head is -1."""
    V, n = 20_000, 400_000
    ranks, _ = traffic.key_stream(MIX, V, 3)
    counts = np.bincount(ranks, minlength=V) - 1      # the once-each copy
    p = traffic.rank_probabilities(MIX["keys"], V)
    assert p[0] / p[9] == pytest.approx(10.0)
    head = np.arange(50)
    want = (n - V) * p[head]
    assert np.all(np.abs(counts[head] - want) < 5 * np.sqrt(want))
    slope = np.polyfit(np.log(head + 1.0), np.log(counts[head]), 1)[0]
    assert slope == pytest.approx(-1.0, abs=0.05)


def test_uniform_and_unknown_law():
    mix = dict(MIX, keys={"distribution": "uniform", "every_key_once": False})
    ranks, _ = traffic.key_stream(mix, 1000, 0)
    counts = np.bincount(ranks, minlength=1000)
    assert counts.std() / counts.mean() < 0.1
    with pytest.raises(ValueError, match="unknown key distribution"):
        traffic.rank_probabilities({"distribution": "pareto"}, 10)
