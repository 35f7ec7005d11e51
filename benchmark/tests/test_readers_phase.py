"""The two reader kinds that read the program's own tracing —
``trace_scope`` (device time by named scope, through the compiled step's
phase map) and ``trace_span`` (host time in, and device idle time under, the
program's spans) — against a hand-made ``xplane.Trace`` whose answers are
worked out in the comments."""

import pytest

from benchmark.lib import spec, xplane
from benchmark.readers import trace_scope, trace_span

STEPS = 2
WINDOW = (0.0, 1000.0)

# what swiftmpi_tpu.obs.costs.phase_map("w2v_step") would return
STEP_MAP = {
    "module": "jit_step", "instructions": 4, "unscoped": 1,
    "phase": {"while.1": "dedup", "fusion.16": "pull",
              "copy.141.remat3": "unscoped", "fusion.24": "apply"}}

DEV0 = xplane.Device(
    "/device:TPU:0",
    op_lines=[[
        # first execution of the step, [100, 500]
        (100, 300, "while.1 s32[]..."),             # self: 200 - 100
        (120, 220, "fusion.16 f32[8,4]"),           # in the while: pull
        (300, 400, "copy.141.remat3 f32[8,4]"),     # a layout copy: no scope
        (400, 500, "fusion.24 f32[8,4]"),           # a fused group: apply
        # another program with an instruction of the same name
        (520, 540, "fusion.16 u32[2]"),
        # second execution, [600, 900]
        (600, 700, "fusion.16 f32[8,4]"),
        (700, 850, "fusion.24 f32[8,4]"),
        # a label op_label left as HLO text; the map does not know it
        (850, 860, "%slice-start.1 = ((s32[1800000]{0:T(1024)}), s32[4505"),
    ]],
    modules=[(100, 500, "jit_step(704314026086775491)"),
             (520, 540, "jit__threefry_split(11937236725742203718)"),
             (600, 900, "jit_step(704314026086775491)")])
DEV1 = xplane.Device(
    "/device:TPU:1",
    op_lines=[[(100, 300, "fusion.24 f32[8,4]")]],
    modules=[(100, 300, "jit_step(704314026086775491)")])

HOST = {
    "python3": [
        (0, 1000, "bench/window"), (10, 990, "bench/train_call"),
        (10, 60, "train_setup"),
        (60, 70, "input_wait"), (62, 68, "bench/next_batch"),
        (70, 80, "h2d"), (80, 95, "dispatch"),
        (95, 105, "input_wait"), (105, 110, "h2d"), (110, 130, "dispatch"),
        (500, 960, "loss_fetch"), (960, 985, "train_finish"),
        (990, 1000, "bench/fence")],
    # another thread's span of the same name is not the train loop's
    "producer": [(0, 1000, "h2d")],
}


def ctx(devices=(DEV0, DEV1)):
    return {"trace": xplane.Trace(list(devices), HOST), "window": WINDOW,
            "steps": STEPS}


def scope(phase, c, monkeypatch, pm=STEP_MAP):
    monkeypatch.setattr(trace_scope, "program_phase_map", lambda name: pm)
    return trace_scope.read({"kind": "trace_scope", "program": "w2v_step",
                             "phase": phase}, c)


def test_trace_scope_by_phase(monkeypatch, capsys):
    c = ctx()
    per = 1e6 * STEPS                      # ns -> ms a step
    # device 0: dedup 100 (the while's self time), pull 100 + 100,
    # apply 100 + 150, unscoped 100 (the copy) + 10 (the unknown label);
    # device 1: apply 200.  Mean over the two devices.
    assert scope("dedup", c, monkeypatch) == pytest.approx(50 / per)
    assert scope("pull", c, monkeypatch) == pytest.approx(100 / per)
    assert scope("apply", c, monkeypatch) == pytest.approx(225 / per)
    assert scope("unscoped", c, monkeypatch) == pytest.approx(55 / per)
    assert scope("math", c, monkeypatch) == 0.0     # ran, took nothing
    # fusion.16 of jit__threefry_split is nobody's pull: the phases sum to
    # the time inside jit_step, (400 + 260 + 200) / 2 devices
    total = sum(scope(p, c, monkeypatch) for p in
                ("sample", "pull", "math", "dedup", "apply", "unscoped"))
    assert total == pytest.approx(430 / per)
    out = capsys.readouterr().out.splitlines()
    assert len(out) == 1 and out[0].startswith("[bench] trace_scope: ")
    assert "1 traced op segments not in the map" in out[0]


def test_trace_scope_reads_nothing_without_a_map_or_a_run(monkeypatch):
    assert scope("pull", ctx(), monkeypatch, pm=None) is None
    other = dict(STEP_MAP, module="jit_multi")      # never executed
    assert scope("pull", ctx(), monkeypatch, pm=other) is None
    # a CPU rehearsal has no device plane
    assert scope("pull", ctx(devices=()), monkeypatch) is None
    assert scope("pull", {"trace": None, "window": None, "steps": 1},
                 monkeypatch) is None


def test_program_phase_map_tolerates_a_program_without_one(monkeypatch):
    from swiftmpi_tpu.obs import costs
    assert trace_scope.program_phase_map("never_tracked") is None
    monkeypatch.delattr(costs, "phase_map")         # the parent commit
    assert trace_scope.program_phase_map("w2v_step") is None


def span(pattern, report, c):
    return trace_span.read({"kind": "trace_span", "span": pattern,
                            "report": report}, c)


def test_trace_span_host_time_and_idle_credit():
    c = ctx()
    per = 1e6 * STEPS
    assert span("^dispatch$", "host_ms_per_step", c) \
        == pytest.approx((15 + 20) / per)
    # the producer thread's h2d is not on the anchor thread
    assert span("^h2d$", "host_ms_per_step", c) \
        == pytest.approx((10 + 5) / per)
    call = "^(train_setup|loss_fetch|train_finish)$"
    assert span(call, "host_ms_per_step", c) \
        == pytest.approx((50 + 460 + 25) / per)
    # device 0 is idle in [0,100] [500,520] [540,600] [860,1000]; under
    # train_setup 50, under loss_fetch 20 + 60 + 100, train_finish 25
    assert span(call, "idle_ms_per_step", c) \
        == pytest.approx(1e3 * (50 + 180 + 25) / 1e9 / STEPS)
    # of the first input_wait's 10 ns, 6 are the harness's inner span;
    # the second is idle until the device starts at 100
    assert span("^input_wait$", "idle_ms_per_step", c) \
        == pytest.approx(1e3 * (4 + 5) / 1e9 / STEPS)
    # a span the program does not emit (the parent commit): nothing
    assert span("^no_such_span$", "host_ms_per_step", c) is None
    assert span("^no_such_span$", "idle_ms_per_step", c) is None
    # a CPU rehearsal has no device plane
    assert span("^dispatch$", "host_ms_per_step", ctx(devices=())) is None


NEW = ["step.sample_ms_per_step", "transfer.pull_ms_per_step",
       "step.math_ms_per_step", "transfer.dedup_ms_per_step",
       "table.apply_ms_per_step", "step.unscoped_ms_per_step",
       "step.dispatch_host_ms_per_step", "input.h2d_host_ms_per_step",
       "step.call_overhead_host_ms_per_step",
       "device.idle_in_call_overhead_ms_per_step"]


def test_the_ten_metrics_resolve():
    assert spec.check() == []
    bench = spec.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW):] == NEW          # appended, in the issue's order
    for cell in bench["workloads"]:
        have = {m["name"]: f for m, f in
                spec.load_cell(cell["name"]).per_layer}
        for name in NEW:
            assert have[name]["cells"] == []
            assert have[name]["moves"] == "words_per_s"
            assert have[name]["reader"]["kind"] in ("trace_scope",
                                                    "trace_span")
