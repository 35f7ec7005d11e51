"""The byte and operation model against a hand count."""

import pytest

from benchmark.costs import w2v as costs
from benchmark.lib import device


def test_rows_and_bytes_hand_count():
    # 2 centers, window 1, 3 negatives, 5-wide f32 rows:
    # targets 2 * (3 + 1) = 8 h rows, contexts 2 * 2 = 4 v rows
    assert costs.rows_per_step(2, 1, 3) == {"pulled": 12, "pushed": 12}
    # pulled: 12 rows * 20 B = 240; pushed: 12 rows * (field read + write
    # 40 B + accumulator read + write 40 B) = 960
    assert costs.step_bytes(2, 1, 3, 5) == 240 + 960


def test_b16k_is_the_344k_rows_of_the_issue():
    rows = costs.rows_per_step(16384, 5, 10)
    assert rows["pulled"] == 344_064
    # 344,064 rows * 1200 B * (1 + 4) passes
    assert costs.step_bytes(16384, 5, 10, 300) == 344_064 * 1200 * 5


def test_flops_hand_count():
    # 1 center, window 1, 1 negative, d = 2: t = 2 target rows, c = 2
    # context rows: neu1 4, f 8, neu1e 8, g * neu1 4, AdaGrad 5 * 4 * 2 = 40
    assert costs.step_flops(1, 1, 1, 2) == 4 + 8 + 8 + 4 + 40


def test_floor_is_memory_bound_and_splits_over_chips():
    peaks = device.peaks_for("TPU v5 lite")
    shape = {"centers": 16384, "window": 5, "negative": 10, "len_vec": 300}
    one = costs.step_floor_seconds(dict(shape, chips=1), peaks)
    four = costs.step_floor_seconds(dict(shape, chips=4), peaks)
    assert one["bound"] == "memory"
    assert one["seconds"] == pytest.approx(344_064 * 6000 / 819e9)
    assert four["seconds"] == pytest.approx(one["seconds"] / 4)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no entry for device_kind"):
        device.peaks_for("TPU v99")
