"""Tier-1 holds ``BENCHMARK.json`` to the contract's limits.

``benchmark/lib/spec.py::check_limits`` refuses a file outside the driver's
limits (``per_layer`` <= 128 among them) at a run's start-up, and
``benchmark/tests/test_spec.py`` is its second witness — but the benchmark's
own tests are not part of tier-1, so a list that outgrew its cap was found
by the first chip run.  This module collects that file's cases here, all of
them and as they are: imported, not copied, and the benchmark's file is
neither moved nor edited.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

pytest.register_assert_rewrite("benchmark.tests.test_spec")

from benchmark.tests.test_spec import (                  # noqa: E402,F401
    test_a_depth_is_no_width, test_a_name_as_the_contract_had_it,
    test_a_second_entry_of_one_reader_is_sent_to_the_first,
    test_benchmark_resolves, test_check_fails_fast_by_name,
    test_contract_limits, test_one_entry_a_reader_and_lists_that_agree,
    test_rehearsal_overlays_toy_sizes_only_when_asked,
    test_unknown_cell_is_named)
