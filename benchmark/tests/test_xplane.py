"""The trace reduction against a hand-made timeline whose answers are worked
out in the comments, and against a small trace recorded on the chip."""

import os

import pytest
from jax.profiler import ProfileData

from benchmark.lib import xplane
from benchmark.readers import (trace_busy, trace_count, trace_idle,
                               trace_ops)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")

# (plane, line, [(name, start_ns, end_ns), ...]); all lines start at 0
HAND = [
    ("/device:TPU:0", "XLA Ops", [
        ("while.1", 100, 500),           # self: 20 + 30 + 150 = 200
        ("fusion.2", 120, 220),          # inside the while
        ("all-reduce.3", 250, 350),      # inside the while
        ("copy.5", 600, 700)]),
    ("/device:TPU:0", "XLA Ops", [       # a second op stream
        ("fusion.4", 300, 340)]),        # runs under the all-reduce
    ("/device:TPU:0", "XLA Modules", [
        ("jit_step", 100, 500), ("jit_step", 600, 700),
        ("jit_late", 900, 950)]),        # starts after the window
    ("/device:TPU:0", "Steps", [("0", 100, 700)]),
    ("/device:TPU:1", "XLA Ops", [("fusion.2", 100, 300)]),
    ("/host:CPU", "main", [
        ("bench/window", 0, 800),
        ("bench/train_call", 50, 560),
        ("PjitFunction(step)", 55, 95),  # no span: ignored
        ("dispatch", 60, 90),
        ("bench/next_batch", 510, 550),
        ("bench/fence", 560, 790)]),
    ("/host:CPU", "producer", [("render", 0, 800)]),   # another thread
]


def hand_profile():
    names = sorted({e[0] for _, _, evs in HAND for e in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    planes = {}
    for plane, line, evs in HAND:
        planes.setdefault(plane, []).append((line, evs))
    text = ""
    for p, (plane, lines) in enumerate(planes.items()):
        text += f'planes {{ id: {p} name: "{plane}"\n'
        for i, (line, evs) in enumerate(lines):
            text += f'  lines {{ id: {i} name: "{line}" timestamp_ns: 0\n'
            for name, s, e in evs:
                text += (f"    events {{ metadata_id: {ids[name]} offset_ps: "
                         f"{s * 1000} duration_ps: {(e - s) * 1000} }}\n")
            text += "  }\n"
        for name, i in ids.items():
            text += (f"  event_metadata {{ key: {i} value {{ id: {i} "
                     f'name: "{name}" }} }}\n')
        text += "}\n"
    return ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def trace():
    return xplane.from_profile(hand_profile())


def test_interval_algebra():
    assert xplane.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert xplane.total([(0, 3), (2, 4), (10, 11)]) == 5
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) \
        == [(0, 2), (3, 5), (7, 9)]
    assert xplane.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert xplane.clip([(0, 10, "a"), (20, 30, "b")], 5, 25) \
        == [(5, 10, "a"), (20, 25, "b")]
    # partial overlap, not nesting: the later start wins while it lasts
    assert xplane.flatten([(0, 10, "a"), (5, 15, "b")]) \
        == [(0, 5, "a"), (5, 15, "b")]
    assert xplane.credit([(0, 10)], [(2, 4, "x"), (4, 6, "y")]) \
        == {xplane.NO_SPAN: 6, "x": 2, "y": 2}


def test_window_busy_idle(trace):
    assert [d.name for d in trace.devices] == ["/device:TPU:0",
                                               "/device:TPU:1"]
    window = trace.window(r"^bench/window$")
    assert window == (0, 800)
    d0, d1 = trace.devices
    # device 0: [100, 500) + [600, 700); fusion.4 lies inside the first
    assert xplane.busy_seconds(d0, window) == pytest.approx(500e-9)
    assert xplane.busy_seconds(d1, window) == pytest.approx(200e-9)
    ctx = {"trace": trace, "window": window, "steps": 2}
    # mean busy (500 + 200) / 2 = 350 ns over 2 steps, in ms
    assert trace_busy.read({}, ctx) == pytest.approx(350e-9 / 2 * 1000)
    # idle: 300 / 800 on device 0, 600 / 800 on device 1: the larger
    assert trace_idle.read({}, ctx) == pytest.approx(75.0)
    # clipping: a window of [150, 650) cuts fusion.2 and copy.5
    assert xplane.busy_seconds(d0, (150, 650)) == pytest.approx(400e-9)


def test_self_time_by_op(trace):
    got = xplane.op_seconds(trace.devices[0], (0, 800))
    want = {"while.1": 200, "fusion.2": 100, "all-reduce.3": 100,
            "copy.5": 100, "fusion.4": 40}
    assert {k: round(v * 1e9) for k, v in got.items()} == want
    assert xplane.top(got, 2)[0][0] == "while.1"


def test_collective_time_and_its_exposed_part(trace):
    d0, d1 = trace.devices
    # all-reduce.3 runs [250, 350); fusion.4 covers [300, 340) of it
    assert xplane.matching_seconds(d0, (0, 800), xplane.COLLECTIVE_OP) \
        == pytest.approx((100e-9, 60e-9))
    assert xplane.matching_seconds(d1, (0, 800), xplane.COLLECTIVE_OP) \
        == (0.0, 0.0)
    ctx = {"trace": trace, "window": (0, 800), "steps": 2}
    coll = {"ops": xplane.COLLECTIVE_OP}
    # mean over the chips (100 + 0) / 2 ns, over 2 steps, in ms
    assert trace_ops.read({**coll, "report": "ms_per_step"}, ctx) \
        == pytest.approx(50e-9 / 2 * 1e3)
    # exposed (60 + 0) / 2 ns of an 800 ns window
    assert trace_ops.read({**coll, "report": "exposed_share"}, ctx) \
        == pytest.approx(100 * 30 / 800)


def test_launches(trace):
    ctx = {"trace": trace, "window": (0, 800), "steps": 2}
    assert xplane.launches(trace.devices[0], (0, 800)) == 2
    assert trace_count.read({}, ctx) == 1.0
    no_modules = xplane.Trace(trace.devices[1:], trace.host_lines)
    assert trace_count.read({}, {**ctx, "trace": no_modules}) is None


def test_idle_gaps_by_host_span(trace):
    # gaps of device 0: [0, 100), [500, 600), [700, 800); the harness
    # thread's innermost spans: window [0, 50) train_call [50, 60) dispatch
    # [60, 90) train_call [90, 510) next_batch [510, 550) train_call
    # [550, 560) fence [560, 790) window [790, 800)
    got = xplane.idle_gaps_by_span(trace, trace.devices[0], (0, 800),
                                   r"^bench/window$",
                                   r"^(bench/|render$|dispatch$)")
    want = {"bench/window": 60, "bench/train_call": 40, "dispatch": 30,
            "bench/next_batch": 40, "bench/fence": 130}
    assert {k: round(v * 1e9) for k, v in got.items()} == want


def test_readers_find_nothing_without_device_planes():
    host_only = xplane.Trace([], {"main": [(0, 10, "bench/window")]})
    ctx = {"trace": host_only, "window": (0, 10), "steps": 1}
    assert trace_busy.read({}, ctx) is None
    assert trace_idle.read({}, ctx) is None
    assert trace_count.read({}, ctx) is None
    assert trace_ops.read({"ops": "x", "report": "ms_per_step"}, ctx) is None


# -- a trace recorded on the chip --------------------------------------------------
# cbow2m-demo on one v5e chip (PR 22's first chip call, seed 2): 2 chunks of
# 20 steps.  Cut to the device's op and module lines and the harness thread's
# spans, stats dropped, HLO text cut to 100 characters.  On this trace no two
# device ops overlap, so busy must equal the plain sum of their durations.

RECORDED = os.path.join(FIXTURES, "cbow2m-demo.v5e.xplane.pb")
STEPS = 40


@pytest.fixture(scope="module")
def recorded():
    return xplane.load(RECORDED)


def test_recorded_trace_busy_is_the_sum_of_its_disjoint_ops(recorded):
    window = recorded.window(r"^bench/window$")
    assert window[1] - window[0] == 6_818_720_657
    (dev,) = recorded.devices
    ops = sorted(dev.ops)
    assert len(ops) == 12_664
    assert all(b[0] >= a[1] for a, b in zip(ops, ops[1:]))
    assert all(window[0] <= o[0] and o[1] <= window[1] for o in ops)
    plain_sum = sum(o[1] - o[0] for o in ops) / 1e9
    assert xplane.busy_seconds(dev, window) == pytest.approx(plain_sum,
                                                             rel=1e-12)
    assert plain_sum == pytest.approx(6.700066411, rel=1e-9)
    ctx = {"trace": recorded, "window": window, "steps": STEPS}
    # what the chip run itself printed for this trace
    assert trace_busy.read({}, ctx) == pytest.approx(167.50166027, rel=1e-9)
    assert trace_idle.read({}, ctx) == pytest.approx(1.74012475, rel=1e-8)
    assert trace_count.read({}, ctx) == 256 / STEPS
    assert trace_ops.read({"ops": xplane.COLLECTIVE_OP,
                           "report": "ms_per_step"}, ctx) == 0.0


def test_recorded_trace_names_the_table_sized_copies(recorded):
    window = recorded.window(r"^bench/window$")
    (dev,) = recorded.devices
    by_op = xplane.op_seconds(dev, window)
    copies = {k: v for k, v in by_op.items()
              if k.startswith("copy.") and k.endswith("f32[2340001,300]")}
    assert len(copies) == 11              # eleven whole-field copies a step
    assert sum(copies.values()) / STEPS * 1e3 == pytest.approx(107.988,
                                                                rel=1e-4)
    grouped = xplane.top(xplane.op_seconds(dev, window, xplane.op_group), 3)
    assert [g[0] for g in grouped] == ["copy f32[2340001,300]",
                                       "fusion f32[2340001,300]",
                                       "fusion s32[1800000]"]
    idle = xplane.idle_gaps_by_span(recorded, dev, window,
                                    r"^bench/window$", r"^(bench/|dispatch$)")
    assert sum(idle.values()) == pytest.approx(
        (window[1] - window[0]) / 1e9 - 6.700066411, rel=1e-9)
    assert max(idle, key=idle.get) == "bench/train_call"


def test_op_labels():
    text = ("%fusion.24 = f32[2340001,300]{1,0:T(8,128)} fusion(f32[2340001,"
            "300]{1,0:T(8,128)} %copy.141.remat3, s32[163840]{0:T(1024)} %x)")
    assert xplane.op_label(text) == "fusion.24 f32[2340001,300]"
    assert xplane.op_label("%copy-start.59 = (u32[500]{0:T(512)}, u32[]) "
                           "copy-start(u32[500] %p)") \
        == "copy-start.59 u32[500]..."
    assert xplane.op_label("bench/window") == "bench/window"
    assert xplane.op_group("copy.141.remat3 f32[2340001,300]") \
        == "copy f32[2340001,300]"
    assert xplane.op_group("all-reduce.7 f32[655360,300]") \
        == "all-reduce f32[655360,300]"
    assert xplane.op_group("pad_add_fusion s32[1800000,4]") \
        == "pad_add_fusion s32[1800000,4]"
