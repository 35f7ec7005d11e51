"""The ``trace_host`` reader (benchmark/readers/trace_host.py, ISSUE 35)
against a hand-made ``xplane.Trace`` whose answers are worked out in the
comments, and the coverage it is there to guard: a ``train()`` call with
telemetry on leaves no stretch of the calling thread outside a span.
"""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import xplane                         # noqa: E402
from benchmark.readers import trace_host, trace_span     # noqa: E402
from swiftmpi_tpu import obs                             # noqa: E402
from swiftmpi_tpu.obs import catalog                     # noqa: E402

STEPS = 2
WINDOW = (0.0, 1000.0)
PER = 1e6 * STEPS             # ns -> ms a step
LOOP = "^(input_wait|step_prep|h2d|dispatch|step_book)$"
CALL = "^(train_setup|loss_fetch|train_finish)$"

# device 0 runs in [100,300] [330,500] and a straggler in [620,640]; it is
# idle in [0,100] [300,330] [500,620] [640,1000] = 100 + 30 + 120 + 360
DEV0 = xplane.Device(
    "/device:TPU:0",
    op_lines=[[(100, 300, "fusion.1 f32[8,4]"),
               (330, 500, "fusion.1 f32[8,4]"),
               (620, 640, "convert.3 f32[]")]],
    modules=[(100, 300, "jit_step(1)"), (330, 500, "jit_step(1)"),
             (620, 640, "jit_convert(2)")])

HOST = {
    "python3": [
        (0, 1000, "bench/window"), (10, 960, "bench/train_call"),
        (10, 60, "train_setup"),
        # step 0: the five siblings, 2 ns bare between book and the wait
        (60, 70, "input_wait"), (62, 68, "bench/next_batch"),
        (70, 76, "step_prep"), (76, 86, "h2d"), (86, 100, "dispatch"),
        (100, 120, "step_book"),
        # step 1
        (122, 130, "input_wait"), (130, 134, "step_prep"),
        (134, 140, "h2d"), (140, 160, "dispatch"), (160, 200, "step_book"),
        # the call's end: loss_wait inside loss_fetch, then 10 ns bare
        (200, 900, "loss_fetch"), (210, 640, "loss_wait"),
        (910, 950, "train_finish"),
        (960, 1000, "bench/fence"),
        # the runtime's own events on the thread are nobody's span
        (86, 99, "PjitFunction(step)")],
    # another thread's span of the same name is not the train loop's
    "producer": [(0, 1000, "render"), (0, 1000, "step_book")],
}


def ctx(devices=(DEV0,), host=HOST):
    return {"trace": xplane.Trace(list(devices), host), "window": WINDOW,
            "steps": STEPS}


def host(report, c, **params):
    return trace_host.read({"kind": "trace_host", "report": report,
                            **params}, c)


def test_spans_declared_and_read_from_the_catalog():
    assert {"step_prep", "step_book", "loss_wait"} <= set(catalog.HOST_SPANS)
    assert trace_host.program_spans() == tuple(catalog.HOST_SPANS)


def test_setup_spans_are_a_list_of_their_own_and_cover_nothing():
    """``SETUP_SPANS`` (ISSUE 52) shares no name with ``HOST_SPANS``, the
    reader's list holds none of them, and a set-up span on the train
    loop's thread — a ``step_build`` in a bare stretch, a ``first_step``
    inside ``dispatch`` — moves no reading: the coverage guard and the
    idle partition read what they read."""
    assert not set(catalog.HOST_SPANS) & set(catalog.SETUP_SPANS)
    assert not set(trace_host.program_spans()) & set(catalog.SETUP_SPANS)
    assert len(set(catalog.SETUP_SPANS)) == len(catalog.SETUP_SPANS)
    with_setup = dict(HOST, python3=HOST["python3"] + [
        (120, 122, "step_build"), (87, 98, "first_step"),
        (88, 90, "kernel_import"), (900, 910, "model_build")])
    reports = [("unspanned_ms_per_step", {}), ("fixed_ms_per_call", {}),
               ("idle_ms_per_step", {"span": LOOP}),
               ("idle_ms_per_step", {"span": CALL}),
               ("idle_ms_per_step", {"span": None})]
    for report, params in reports:
        assert host(report, ctx(host=with_setup), **params) ==             host(report, ctx(), **params), report
    assert host("unspanned_ms_per_step", ctx(host=with_setup)) \
        == pytest.approx((2 + 10 + 10) / PER)


def test_unspanned_is_the_call_less_the_spans():
    # bench/train_call is [10,960]; bare: [120,122] after step 0's book,
    # [900,910] before train_finish, [950,960] after it
    assert host("unspanned_ms_per_step", ctx()) \
        == pytest.approx((2 + 10 + 10) / PER)


def test_idle_goes_to_the_innermost_program_span():
    c = ctx()
    ms = 1e3 / 1e9 / STEPS
    # [0,100]: 10 bare, train_setup 50, input_wait 10 (bench/next_batch
    # inside it takes nothing), step_prep 6, h2d 10, dispatch 14
    assert host("idle_ms_per_step", c, span="^input_wait$") \
        == pytest.approx(10 * ms)
    assert host("idle_ms_per_step", c, span="^dispatch$") \
        == pytest.approx(14 * ms)
    # [300,330], [500,620] and [640,900] lie under loss_fetch, the first
    # two inside loss_wait: the child takes them
    assert host("idle_ms_per_step", c, span="^loss_wait$") \
        == pytest.approx((30 + 120) * ms)
    assert host("idle_ms_per_step", c, span="^loss_fetch$") \
        == pytest.approx(260 * ms)
    assert host("idle_ms_per_step", c, span=LOOP) \
        == pytest.approx((10 + 6 + 10 + 14) * ms)
    # no program span open: [0,10], [900,910], [950,1000]
    assert host("idle_ms_per_step", c, span=None) \
        == pytest.approx((10 + 10 + 50) * ms)


def test_three_idle_metrics_partition_the_idle_time():
    """``device.idle_in_loop`` + ``device.idle_in_call_overhead`` (the
    accepted ``trace_span`` metric, whose frozen list has never heard of
    ``loss_wait`` and so leaves its idle with ``loss_fetch``) +
    ``device.idle_unattributed`` = all of device 0's idle time."""
    c = ctx()
    in_loop = host("idle_ms_per_step", c, span=LOOP)
    in_call = trace_span.read({"kind": "trace_span", "span": CALL,
                               "report": "idle_ms_per_step"}, c)
    bare = host("idle_ms_per_step", c, span=None)
    assert in_call == pytest.approx(1e3 * (50 + 410 + 40) / 1e9 / STEPS)
    idle = xplane.total(xplane.gaps(DEV0.ops, *WINDOW))
    assert idle == 610
    assert in_loop + in_call + bare == pytest.approx(1e3 * idle / 1e9 / STEPS)


def test_fixed_cost_of_a_call_leaves_the_wait_out():
    # train_setup 50 + loss_fetch 700 - loss_wait 430 + train_finish 40,
    # one train_setup in the window
    assert host("fixed_ms_per_call", ctx()) \
        == pytest.approx((50 + 270 + 40) / 1e6)
    twice = dict(HOST, python3=HOST["python3"] + [(955, 958, "train_setup")])
    assert host("fixed_ms_per_call", ctx(host=twice)) \
        == pytest.approx((50 + 270 + 40 + 3) / 1e6 / 2)
    # the old union, wait and all, for comparison
    assert trace_span.read({"kind": "trace_span", "span": CALL,
                            "report": "host_ms_per_step"}, ctx()) \
        == pytest.approx((50 + 700 + 40) / PER)


def test_a_span_the_reader_never_heard_of(monkeypatch):
    """A later PR declares ``eval_pause`` and opens it in the bare stretch
    before ``train_finish``: credited with no edit of the reader."""
    later = dict(HOST, python3=HOST["python3"] + [(900, 910, "eval_pause")])
    c = ctx(host=later)
    assert host("idle_ms_per_step", c, span="^eval_pause$") is None
    monkeypatch.setattr(catalog, "HOST_SPANS",
                        catalog.HOST_SPANS + ("eval_pause",))
    ms = 1e3 / 1e9 / STEPS
    assert host("idle_ms_per_step", c, span="^eval_pause$") \
        == pytest.approx(10 * ms)
    assert host("idle_ms_per_step", c, span=None) \
        == pytest.approx((10 + 50) * ms)
    assert host("unspanned_ms_per_step", c) == pytest.approx((2 + 10) / PER)


@pytest.mark.parametrize("report,params", [
    ("unspanned_ms_per_step", {}),
    ("idle_ms_per_step", {"span": LOOP}),
    ("idle_ms_per_step", {"span": None}),
    ("fixed_ms_per_call", {}),
])
def test_nothing_to_read_is_none(report, params):
    # a CPU rehearsal has no device plane
    assert host(report, ctx(devices=()), **params) is None
    assert host(report, {"trace": None, "window": None, "steps": 1},
                **params) is None
    # a trace with none of the program's spans on the anchor thread
    bare = {"python3": [(0, 1000, "bench/window"),
                        (10, 960, "bench/train_call")]}
    assert host(report, ctx(host=bare), **params) is None


def test_a_commit_that_predates_the_new_spans():
    """The parent's trace: the old six spans only.  The readers of the new
    spans read nothing and do not raise; the guard reads what is bare."""
    old = {"python3": [e for e in HOST["python3"] if e[2] not in
                       ("step_prep", "step_book", "loss_wait")]}
    c = ctx(host=old)
    assert host("idle_ms_per_step", c, span="^step_book$") is None
    assert trace_span.read({"kind": "trace_span", "span": "^loss_wait$",
                            "report": "host_ms_per_step"}, c) is None
    # bare now: both preps and books (6 + 20 + 4 + 40) and the 22 of before
    assert host("unspanned_ms_per_step", c) \
        == pytest.approx((70 + 22) / PER)
    # train_setup 50 + loss_fetch 700 + train_finish 40: the wait is in
    assert host("fixed_ms_per_call", c) == pytest.approx(790 / 1e6)
    with pytest.raises(ValueError):
        host("no_such_report", c)


# -- the coverage the reader guards, on the CPU ---------------------------------

def _toy_model(telemetry):
    from swiftmpi_tpu.models.word2vec import Word2Vec
    from swiftmpi_tpu.utils import ConfigParser

    worker = {"minibatch": 2048}
    if telemetry:       # ring buffer only: no JSONL file
        worker.update({"telemetry": 1, "telemetry_path": ""})
    return Word2Vec(config=ConfigParser().update({
        "cluster": {"transfer": "xla"},
        "word2vec": {"len_vec": 32, "window": 4, "negative": 5,
                     "sample": -1, "learning_rate": 0.05,
                     "min_sentence_length": 2},
        "server": {"initial_learning_rate": 0.3}, "worker": worker}))


def _phase_sums():
    hists = obs.get_registry().snapshot()["hists"]
    return {k[len("phase_ms{phase="):-1]: (v["count"], v["sum"])
            for k, v in hists.items() if k.startswith("phase_ms{")}


def test_train_call_is_covered_by_its_spans():
    """Between ``train_setup``'s start and ``train_finish``'s end the
    calling thread is in a span: the ``phase_ms`` samples of one call
    (``loss_wait`` is inside ``loss_fetch``, so not added twice) sum to the
    call's wall time to 5 %."""
    from swiftmpi_tpu.data.text import synthetic_corpus

    corpus = synthetic_corpus(400, vocab_size=300, length=30, seed=8)
    m = _toy_model(telemetry=True)
    m.train(corpus, niters=1)              # builds, compiles, arms the plane
    before = _phase_sums()
    t0 = time.perf_counter()
    m.train(corpus, niters=1)
    wall_ms = (time.perf_counter() - t0) * 1e3
    after = _phase_sums()
    got = {k: (n - before.get(k, (0, 0.0))[0], s - before.get(k, (0, 0.0))[1])
           for k, (n, s) in after.items()}
    steps = got["dispatch"][0]
    assert steps > 3
    assert got["step_prep"][0] == got["step_book"][0] == got["h2d"][0] \
        == got["input_wait"][0] == steps
    assert got["train_setup"][0] == got["loss_fetch"][0] \
        == got["loss_wait"][0] == got["train_finish"][0] == 1
    assert got["loss_wait"][1] <= got["loss_fetch"][1]
    covered = sum(s for k, (_n, s) in got.items() if k != "loss_wait")
    assert covered <= wall_ms
    assert covered >= 0.95 * wall_ms, (got, wall_ms)


def test_new_spans_are_the_shared_noop_with_telemetry_off():
    from swiftmpi_tpu.data.text import synthetic_corpus

    assert not obs.get_registry().enabled
    null = obs.span("render")
    for name in ("step_prep", "step_book", "loss_wait"):
        assert obs.span(name, step=3) is null
    m = _toy_model(telemetry=False)
    m.train(synthetic_corpus(40, vocab_size=60, length=14, seed=8),
            niters=1)
    assert not obs.get_registry().enabled and _phase_sums() == {}
