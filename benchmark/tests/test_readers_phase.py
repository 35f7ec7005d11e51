"""The two reader kinds that read the program's own tracing —
``trace_scope`` (device time by named scope, through the compiled step's
phase map) and ``trace_span`` (host time in, and device idle time under, the
program's spans) — against a hand-made ``xplane.Trace`` whose answers are
worked out in the comments."""

import pytest

from benchmark.lib import spec, xplane
from benchmark.readers import trace_ops, trace_scope, trace_span

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
        (850, 855, "%slice-start.1 = ((s32[1800000]{0:T(1024)}), s32[4505"),
    ]],
    modules=[(100, 500, "jit_step(704314026086775491)"),
             (520, 540, "jit__threefry_split(11937236725742203718)"),
             (600, 900, "jit_step(704314026086775491)")])
DEV1 = xplane.Device(
    "/device:TPU:1",
    op_lines=[[(100, 300, "fusion.24 f32[8,4]")]],
    modules=[(100, 300, "jit_step(704314026086775491)")])
# a plane the profiler labelled by region, not by instruction (one of a
# four-chip trace's, PERF.md section 6): the same step, nothing the map knows
DEV2 = xplane.Device(
    "/device:TPU:2",
    op_lines=[[(100, 300, "region.41"), (300, 380, "region.7"),
               (380, 500, "region.41"), (600, 900, "fusion.24 f32[8,4]")]],
    modules=[(100, 500, "jit_step(704314026086775491)"),
             (600, 900, "jit_step(704314026086775491)")])

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
    # apply 100 + 150, unscoped 100 (the copy) + 5 (the unknown label: a
    # name the map does not know is unscoped time, whatever its share);
    # device 1: apply 200.  Mean over the two devices.
    assert scope("dedup", c, monkeypatch) == pytest.approx(50 / per)
    assert scope("pull", c, monkeypatch) == pytest.approx(100 / per)
    assert scope("apply", c, monkeypatch) == pytest.approx(225 / per)
    assert scope("unscoped", c, monkeypatch) == pytest.approx(52.5 / per)
    assert scope("math", c, monkeypatch) == 0.0     # ran, took nothing
    # fusion.16 of jit__threefry_split is nobody's pull: the phases sum to
    # the time inside jit_step, (400 + 255 + 200) / 2 devices
    total = sum(scope(p, c, monkeypatch) for p in
                ("sample", "pull", "math", "dedup", "apply", "unscoped"))
    assert total == pytest.approx(427.5 / per)
    out = capsys.readouterr().out.splitlines()
    assert len(out) == 1 and out[0].startswith("[bench] trace_scope: ")
    assert "1 traced op segments not in the map" in out[0]
    assert "left out" not in out[0]


def test_a_plane_of_region_labels_is_left_out_by_every_name_reader(
        monkeypatch, capsys):
    """A plane with ``region.<n>`` labels over 400 of its 700 busy ns.  Read
    as before it would put 400 / 3 devices under ``unscoped`` and take a
    third from every scope; ``xplane.named_devices`` leaves it out of the
    means by op name instead — for ``trace_scope``, ``trace_ops`` and the
    breakdown alike — and it is named.  Busy time reads all three planes."""
    per = 1e6 * STEPS
    c = ctx(devices=(DEV0, DEV1, DEV2))
    assert scope("apply", c, monkeypatch) == pytest.approx(225 / per)
    assert scope("unscoped", c, monkeypatch) == pytest.approx(52.5 / per)
    assert scope("dedup", c, monkeypatch) == pytest.approx(50 / per)
    out = capsys.readouterr().out.splitlines()
    assert len(out) == 1
    assert "1 traced op segments not in the map" in out[0]
    assert out[0].endswith("; left out of the means by op name: "
                           "/device:TPU:2, 57.1% of its busy time under "
                           "region.<n> labels")
    # the kernel beside its scope: fusion.24 is 250 + 200 on the two planes
    # read (300 more on the third, whose other 400 could be fusion.24 too)
    ops = {"ops": r"^fusion\.24", "report": "ms_per_step"}
    assert trace_ops.read(ops, c) == pytest.approx(225 / per)
    # every plane like that (one chip): all are read, as before the rule
    alone = ctx(devices=(DEV2,))
    assert scope("unscoped", alone, monkeypatch) == pytest.approx(400 / per)
    assert scope("apply", alone, monkeypatch) == pytest.approx(300 / per)
    assert trace_ops.read(ops, alone) == pytest.approx(300 / per)
    assert "left out" not in capsys.readouterr().out
    # under UNNAMED_SHARE of the plane's busy time (7 of 707): read
    short = xplane.Device("/device:TPU:3", op_lines=[[
        (100, 107, "region.41"), (200, 900, "fusion.24 f32[8,4]")]],
        modules=[(100, 900, "jit_step(704314026086775491)")])
    c = ctx(devices=(DEV1, short))
    assert xplane.named_devices(c["trace"], WINDOW) == ([DEV1, short], [])
    assert scope("unscoped", c, monkeypatch) == pytest.approx(3.5 / per)


def test_a_stale_map_drops_no_plane(monkeypatch, capsys):
    """Names the map does not know are no reason to leave a plane out: with
    a map that lacks ``fusion.24`` both planes are read and its time is
    unscoped, as it was before PR 51."""
    per = 1e6 * STEPS
    stale = dict(STEP_MAP, phase={k: v for k, v in STEP_MAP["phase"].items()
                                  if k != "fusion.24"})
    c = ctx()
    assert scope("apply", c, monkeypatch, pm=stale) == 0.0
    assert scope("unscoped", c, monkeypatch, pm=stale) \
        == pytest.approx((105 + 250 + 200) / 2 / per)
    out = capsys.readouterr().out
    assert "4 traced op segments not in the map (" in out
    assert "left out" not in out


def test_the_breakdown_reads_the_planes_the_readers_read(monkeypatch,
                                                         capsys):
    from benchmark import run as harness

    trace = ctx(devices=(DEV0, DEV1, DEV2))["trace"]
    monkeypatch.setattr(xplane, "load", lambda path: trace)
    _t, window, block, breakdown = harness.reduce_trace("kept", STEPS)
    assert window == WINDOW
    # busy: every plane, (675 + 200 + 700) / 3 ns
    assert block["busy_s"] == pytest.approx(525e-9)
    ops = dict(breakdown["device_ops"])
    assert ops["fusion f32[8,4]"] == pytest.approx((450 + 200) / 2 * 1e-9)
    assert "region" not in ops
    assert "left out of the means by op name: /device:TPU:2" \
        in capsys.readouterr().out


def test_trace_ops_takes_both_spellings_of_a_collective():
    """``transfer.collective_*``'s pattern against XLA's opcode names and
    the names an instruction lowered from a ``jax.lax`` collective carries
    (``transfer/route.py``'s exchange is ``all_to_all.<n>`` in a trace)."""
    f = spec.load_json(spec.bench_path(
        "layer_metrics", "transfer.collective_ms_per_step.json"))
    assert f["reader"]["ops"] == xplane.COLLECTIVE_OP
    dev = xplane.Device("/device:TPU:0", op_lines=[[
        (0, 100, "all_to_all.3 f32[4,34384,384]"),
        (100, 130, "fusion.1 f32[8,4]"),
        (130, 150, "all-to-all.1 f32[4,8,384]"),
        (150, 160, "psum.2 s32[]"), (160, 170, "pmax.1 s32[]"),
        (170, 180, "pmin.4 s32[]"), (180, 190, "all-reduce.9 f32[8]"),
        (190, 200, "all_gather.1 f32[8]"),
        (200, 210, "collective-permute-start.1 f32[8]"),
        (210, 220, "ppermute.1 f32[8]"),
        (220, 230, "reduce-scatter.1 f32[8]"),
        # not collectives: a fusion that feeds one, a name that only holds one
        (230, 300, "fusion.all_to_all f32[8]"),
        (300, 400, "rmw_tiles f32[975001,384]...")]])
    c = {"trace": xplane.Trace([dev], HOST), "window": WINDOW, "steps": STEPS}
    ms = trace_ops.read(dict(f["reader"], report="ms_per_step"), c)
    assert ms == pytest.approx((100 + 100) / (1e6 * STEPS))
    # the parent's pattern saw the opcode spellings only
    old = "^(all-to-all|all-reduce|all-gather|reduce-scatter|" \
          "collective-permute)"
    assert trace_ops.read({"ops": old, "report": "ms_per_step"}, c) \
        == pytest.approx((20 + 10 + 10 + 10) / (1e6 * STEPS))
    # the two kernel metrics read their custom calls by name
    rmw = spec.load_json(spec.bench_path(
        "layer_metrics", "table.rmw_kernel_ms_per_step.json"))["reader"]
    assert trace_ops.read(rmw, c) == pytest.approx(100 / (1e6 * STEPS))


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
    # device 0 is idle in [0,100] [500,520] [540,600] [855,1000]; under
    # train_setup 50, under loss_fetch 20 + 60 + 105, train_finish 25
    assert span(call, "idle_ms_per_step", c) \
        == pytest.approx(1e3 * (50 + 185 + 25) / 1e9 / STEPS)
    # of the first input_wait's 10 ns, 6 are the harness's inner span;
    # the second is idle until the device starts at 100
    assert span("^input_wait$", "idle_ms_per_step", c) \
        == pytest.approx(1e3 * (4 + 5) / 1e9 / STEPS)
    # a span the program does not emit (the parent commit): nothing
    assert span("^no_such_span$", "host_ms_per_step", c) is None
    assert span("^no_such_span$", "idle_ms_per_step", c) is None
    # a CPU rehearsal has no device plane
    assert span("^dispatch$", "host_ms_per_step", ctx(devices=())) is None


def test_a_child_span_leaves_its_idle_time_with_the_call_level_span():
    """``loss_wait`` opens inside ``loss_fetch`` (PR 35): the idle time
    under it still reads under ``device.idle_in_call_overhead_ms_per_step``,
    whose reader credits by its own list of call-level spans."""
    f = spec.load_json(spec.bench_path(
        "layer_metrics", "device.idle_in_call_overhead_ms_per_step.json"))
    before = trace_span.read(f["reader"], ctx())
    host = dict(HOST, python3=HOST["python3"] + [(510, 900, "loss_wait")])
    c = {"trace": xplane.Trace([DEV0, DEV1], host), "window": WINDOW,
         "steps": STEPS}
    assert trace_span.read(f["reader"], c) == before \
        == pytest.approx(1e3 * (50 + 185 + 25) / 1e9 / STEPS)
    assert span("^loss_wait$", "host_ms_per_step", c) \
        == pytest.approx(390 / (1e6 * STEPS))


W2V_PHASES = ["step.sample_ms_per_step", "transfer.pull_ms_per_step",
              "step.math_ms_per_step", "transfer.dedup_ms_per_step",
              "table.apply_ms_per_step", "step.unscoped_ms_per_step"]
HOST_SPAN_METRICS = [
    "step.dispatch_host_ms_per_step", "input.h2d_host_ms_per_step",
    "step.prep_host_ms_per_step", "step.book_host_ms_per_step",
    "step.loss_wait_host_ms_per_step", "step.call_fixed_host_ms_per_call",
    "step.unspanned_host_ms_per_step", "device.idle_in_loop_ms_per_step",
    "device.idle_unattributed_ms_per_step",
    "device.idle_in_call_overhead_ms_per_step"]


def test_the_phase_and_span_metrics_resolve():
    """The six ``w2v_step`` phases read in the cells whose family runs that
    program (no family gives another program its name any more), a
    ``trainer_step`` scope never in them; the host-span metrics have no
    list: both train loops emit every span, so a new cell joins unasked."""
    assert spec.check() == []
    bench = spec.load_benchmark()
    for cell in bench["workloads"]:
        loaded = spec.load_cell(cell["name"])
        have = {m["name"]: f for m, f in loaded.per_layer}
        listed = {m["name"] for m, _f in loaded.per_layer if "workloads" in m}
        programs = {f["reader"]["program"] for f in have.values()
                    if f["reader"]["kind"] in ("trace_scope",
                                               "scope_roofline")}
        w2v = loaded.family.startswith("w2v")
        assert programs == ({"w2v_step"} if w2v else {"trainer_step"})
        assert set(W2V_PHASES) <= set(have) if w2v \
            else not set(W2V_PHASES) & set(have)
        for name in HOST_SPAN_METRICS:
            assert name not in listed
            assert have[name]["moves"] == "words_per_s"
            assert have[name]["reader"]["kind"] in ("trace_span",
                                                    "trace_host")
    assert "step.call_overhead_host_ms_per_step" not in \
        {m["name"] for m in bench["per_layer"]}


def test_a_kept_trace_is_read_again_by_the_tool(monkeypatch, tmp_path, capsys):
    """``tools/read_kept_trace.py`` on a hand-made kept directory: the maps
    come from ``context.json`` through ``ctx["phase_maps"]`` (nothing of the
    program is asked, no module is patched), and each metric reads what its
    reader gives on the same trace."""
    import importlib.util
    import json
    import os

    path = os.path.join(spec.BENCH_DIR, "tools", "read_kept_trace.py")
    tool_spec = importlib.util.spec_from_file_location("read_kept_trace", path)
    tool = importlib.util.module_from_spec(tool_spec)
    tool_spec.loader.exec_module(tool)
    (tmp_path / "host.xplane.pb").write_bytes(b"")
    (tmp_path / "context.json").write_text(json.dumps({
        "cell": "cbow2m-demo", "steps": STEPS, "harness": {}, "counters": [],
        "memory": {"peak_bytes": None, "table_bytes_per_device": 0},
        "floor": None, "phase_maps": {"w2v_step": STEP_MAP}}))
    made = ctx()
    made["trace"].window = lambda anchor: WINDOW
    monkeypatch.setattr(xplane, "load", lambda path: made["trace"])
    monkeypatch.setattr(trace_scope, "program_phase_map", lambda name: (
        pytest.fail("the kept maps are the ones to read with")))
    assert tool.main([str(tmp_path)]) == 0
    got = json.loads(capsys.readouterr().out.splitlines()[-1])
    per = 1e6 * STEPS
    assert got["table.apply_ms_per_step"] == pytest.approx(225 / per)
    assert got["transfer.dedup_ms_per_step"] == pytest.approx(50 / per)
    assert got["step.unscoped_ms_per_step"] == pytest.approx(52.5 / per)
    assert not [n for n in got if n.startswith("lm.")]   # no LM cell's
    (tmp_path / "second.xplane.pb").write_bytes(b"")
    with pytest.raises(SystemExit, match="expected one .xplane.pb"):
        tool.main([str(tmp_path)])
