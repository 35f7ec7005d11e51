"""Compiler & cost observability tests (ISSUE 14, obs/costs.py +
obs/profiler.py): the TrackedFn compile/retrace bookkeeping against a
real jit cache, hand-model drift math, the w2v cost-catalog golden on
CPU (compile/* series in the JSONL + a valid smtpu-costs/1 artifact +
the --compile report rendering it), the shape-churn -> retrace-counter
-> budget-gate acceptance path, triggered profiler windows (profile_at
knob artifacts, the fleet trigger file, the xplane phase reduction),
and the off-by-default bit-identity contract across the jit-stepped
transfer backends.
"""

import glob
import json
import os
import sys
import weakref

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from swiftmpi_tpu import obs  # noqa: E402
from swiftmpi_tpu.data.text import synthetic_corpus  # noqa: E402
from swiftmpi_tpu.models.word2vec import Word2Vec  # noqa: E402
from swiftmpi_tpu.obs import costs as obs_costs  # noqa: E402
from swiftmpi_tpu.obs import profiler as obs_profiler  # noqa: E402
from swiftmpi_tpu.utils import ConfigParser  # noqa: E402

SCRIPTS = os.path.join(os.path.dirname(__file__), os.pardir, "scripts")


def _scripts_on_path():
    if SCRIPTS not in sys.path:
        sys.path.insert(0, SCRIPTS)


def _corpus():
    return synthetic_corpus(40, vocab_size=60, length=14, seed=8)


def _cfg(transfer="xla", path=None, obs_extra=None):
    d = {
        "cluster": {"transfer": transfer},
        "word2vec": {"len_vec": 16, "window": 2, "negative": 5,
                     "sample": -1, "learning_rate": 0.05,
                     "min_sentence_length": 2},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 512},
    }
    if path is not None:
        d["worker"].update({"telemetry": 1, "telemetry_path": path,
                            "telemetry_flush": 1})
    if obs_extra:
        d["obs"] = dict(obs_extra)
    return ConfigParser().update(d)


def _train_final(cfg, corp, niters=3, batch_size=64):
    m = Word2Vec(config=cfg)
    losses = m.train(corp, niters=niters, batch_size=batch_size)
    params = {k: np.asarray(v) for k, v in m.table.state.items()}
    return losses, params, m


def _lines(path):
    return [json.loads(ln) for ln in open(path) if ln.strip()]


def _counter_total(path, name):
    """Sum one counter series (any labels) across a JSONL stream's
    step deltas (the summary line repeats the totals — skip it)."""
    total = 0.0
    for rec in _lines(path):
        if rec.get("kind") != "step":
            continue
        for key, delta in (rec.get("counters") or {}).items():
            if key.split("{", 1)[0] == name:
                total += delta
    return total


def _arm(tmp_path, memory=False):
    cat = obs_costs.get_catalog()
    cat.enabled = True
    cat.memory = memory
    cat.path = str(tmp_path / "compile_catalog.json")
    obs.set_enabled(True)
    return cat


# -- TrackedFn unit: compiles, cache hits, retraces ------------------------

def test_trackedfn_books_compiles_and_retraces(tmp_path):
    """Booked from JAX's own stage events on the calling thread (the
    start-up ledger's listeners, ISSUE 52): a call during which a program
    was lowered is a compile, one in which the handle also traced after a
    compile it booked a retrace, and ``compile_ms`` is the stages' own
    durations — no execution, no probe of the jit's cache."""
    cat = _arm(tmp_path, memory=True)

    def unit_fn(x):
        return x * 2.0 + 1.0

    def stage_ms(program=None):
        """What the ledger holds (of ``program``), in milliseconds."""
        return 1e3 * sum(r["seconds"] for r in obs.setup_report()["stages"]
                         if program in (None, r["program"]))

    f = obs_costs.track("unit_fn", jax.jit(unit_fn))
    x = jnp.ones((8,), jnp.float32)
    before = stage_ms()
    np.testing.assert_allclose(np.asarray(f(x)), np.full(8, 3.0, "f4"))
    e = cat.entry("unit_fn")
    assert e["compiles"] == 1 and e["retraces"] == 0
    # the stages' own durations as the ledger has them, the inner
    # primitives' tracing with the program's (the analysis below lowers
    # and compiles once more, outside the call: the ledger has that too)
    first_ms = e["compile_ms_total"]
    assert 0.0 < first_ms == e["last_compile_ms"]
    assert first_ms <= stage_ms() - before
    assert stage_ms("unit_fn") > 0.0
    # XLA's own numbers landed (cost_analysis + memory_analysis)
    assert e["flops"] > 0 and e["bytes_accessed"] > 0
    assert e["peak_bytes"] > 0

    # same shape again: a cached dispatch emits no event, nothing booked
    f(x + 1.0)
    assert cat.entry("unit_fn")["compiles"] == 1
    assert cat.entry("unit_fn")["compile_ms_total"] == first_ms

    # shape churn on the SAME handle: compile + retrace
    f(jnp.ones((16,), jnp.float32))
    e = cat.entry("unit_fn")
    assert e["compiles"] == 2 and e["retraces"] == 1
    assert e["compile_ms_total"] == pytest.approx(
        first_ms + e["last_compile_ms"])

    # ...but a FRESH handle under the same name (control-plane rebuild,
    # fused-cache growth) books a compile, never a retrace
    g = obs_costs.track("unit_fn", jax.jit(lambda x: x * 2.0 + 1.0))
    g(x)
    e = cat.entry("unit_fn")
    assert e["compiles"] == 3 and e["retraces"] == 1

    # a handle called under a trace is inlined there: it lowers nothing,
    # and books nothing
    jax.jit(lambda x: g(x) + 1.0)(jnp.ones((24,), jnp.float32))
    assert cat.entry("unit_fn")["compiles"] == 3

    # the series keep their names
    counters = obs.get_registry().snapshot()["counters"]
    assert counters["compile/compiles{fn=unit_fn}"] == 3
    assert counters["compile/retraces{fn=unit_fn}"] == 1
    assert counters["compile/compile_ms{fn=unit_fn}"] == pytest.approx(
        e["compile_ms_total"])

    # the crash-safe artifact validates
    doc = json.load(open(cat.path))
    assert doc["schema"] == obs_costs.COSTS_SCHEMA
    assert doc["fns"]["unit_fn"]["compiles"] == 3


def test_trackedfn_disarmed_is_passthrough_and_weakrefable():
    f = obs_costs.track("quiet_fn", jax.jit(lambda x: x + 1.0))
    # jax weakrefs the step callable — the wrapper must support it
    assert weakref.ref(f)() is f
    # idempotent: re-tracking returns the same wrapper
    assert obs_costs.track("other_name", f) is f
    f(jnp.ones((4,), jnp.float32))
    f(jnp.ones((9,), jnp.float32))   # would be a retrace if armed
    assert obs_costs.get_catalog().entry("quiet_fn") is None
    # attribute forwarding reaches the wrapped jit
    assert f._cache_size() == 2


def test_hand_model_drift(tmp_path):
    cat = _arm(tmp_path)
    f = obs_costs.track("drift_fn", jax.jit(lambda x: x @ x))
    f(jnp.ones((8, 8), jnp.float32))
    measured = cat.entry("drift_fn")["flops"]
    cat.note_hand_model("drift_fn", flops=measured * 1.25,
                        bytes_accessed=cat.entry("drift_fn")
                        ["bytes_accessed"])
    fns = cat.snapshot()["fns"]
    assert fns["drift_fn"]["flops_drift_pct"] == pytest.approx(25.0)
    assert fns["drift_fn"]["bytes_drift_pct"] == pytest.approx(0.0)


# -- w2v cost-catalog + profile_at golden on CPU ---------------------------

@pytest.mark.slow
def test_w2v_costs_catalog_and_profile_at_golden(tmp_path, devices8):
    """Armed ``[obs] costs`` + ``profile_at`` on ONE small CPU w2v run
    (two e2e surfaces, one train — tier-1 wall clock matters):
    compile/*{fn=} series land in the JSONL, the smtpu-costs/1 artifact
    validates with measured flops/bytes for a w2v step, the --compile
    report renders both, and a bounded trace lands under profile_dir
    with a parsing profile_summary.json that the stream saw."""
    tel = str(tmp_path / "tel.jsonl")
    cat_path = str(tmp_path / "compile_catalog.json")
    prof_dir = str(tmp_path / "profiles")
    _train_final(_cfg("xla", path=tel,
                      obs_extra={"costs": 1, "costs_path": cat_path,
                                 "costs_memory": 0,
                                 "profile_at": 1, "profile_steps": 2,
                                 "profile_dir": prof_dir}),
                 _corpus())

    # JSONL: the funnel counted at least one compile, zero retraces
    assert _counter_total(tel, "compile/compiles") >= 1
    assert _counter_total(tel, "compile/retraces") == 0
    gauges = set()
    for rec in _lines(tel):
        gauges |= set(rec.get("gauges") or {})
    assert any(g.startswith("compile/flops{") for g in gauges)

    # artifact: valid schema, measured numbers for a w2v step fn
    doc = json.load(open(cat_path))
    assert doc["schema"].startswith(obs_costs.COSTS_SCHEMA_PREFIX)
    w2v_fns = {k: v for k, v in doc["fns"].items()
               if k.startswith("w2v")}
    assert w2v_fns, doc["fns"].keys()
    assert any(v.get("flops", 0) > 0 and v.get("bytes_accessed", 0) > 0
               for v in w2v_fns.values())
    assert all(v["retraces"] == 0 for v in doc["fns"].values())

    # the report renders a compile section from stream + artifact
    _scripts_on_path()
    import telemetry_report
    comp = telemetry_report.compile_summary(telemetry_report.load(tel),
                                            catalog=doc)
    assert comp["retraces_total"] == 0
    assert comp["compile_ms_total"] > 0
    assert any(f.startswith("w2v") for f in comp["fns"])
    assert telemetry_report.main(
        [tel, "--compile", "--catalog", cat_path]) == 0

    # profile_at: the bounded capture landed and parsed
    dirs = glob.glob(os.path.join(prof_dir, "profile_step*_r*"))
    assert len(dirs) == 1
    summary = json.load(open(os.path.join(dirs[0],
                                          "profile_summary.json")))
    assert summary["schema"] == obs_profiler.PROFILE_SCHEMA
    assert summary["reason"] == "profile_at"
    assert summary["steps"] >= 1
    assert summary["files"] >= 1       # the raw trace actually landed
    assert summary["events"] > 0
    assert isinstance(summary["device_ms"], dict)
    # ...and the stream saw it: counters + the capture event
    assert _counter_total(tel, "profile/sessions") == 1
    assert _counter_total(tel, "profile/steps") >= 1
    caps = [r for r in _lines(tel) if r.get("kind") == "profile/capture"]
    assert len(caps) == 1 and caps[0]["run_dir"] == dirs[0]


# -- shape churn -> retrace counter -> budget gate -------------------------

def _emit_run(tmp_path, name, shapes):
    """One synthetic 'run': an armed tracked jit driven through
    ``shapes``, wire counters riding along, recorded to JSONL — the
    minimal stream check_traffic_budget can cell-ify."""
    reg = obs.reset_for_tests()
    obs.set_enabled(True)
    cat = obs_costs.get_catalog()
    cat.enabled, cat.memory = True, False
    path = str(tmp_path / f"{name}.jsonl")
    rec = obs.StepRecorder(reg, path=path, run="w2v", flush_every=1)
    f = obs_costs.track("w2v_step", jax.jit(lambda x: (x * 2.0).sum()))
    for n in shapes:
        f(jnp.ones((n,), jnp.float32))
        reg.counter("transfer/wire_bytes", backend="xla").inc(1024)
        reg.counter("transfer/dispatches", backend="xla").inc(1)
        rec.on_steps(1)
    rec.close()
    return path


def test_shape_churn_trips_retrace_budget_gate(tmp_path, capsys):
    base = _emit_run(tmp_path, "base", [8, 8, 8])       # steady state
    cand = _emit_run(tmp_path, "cand", [8, 12, 16])     # churning
    _scripts_on_path()
    import check_traffic_budget as ctb
    b, c = ctb.load_cells(base), ctb.load_cells(cand)
    assert b["w2v"]["retraces"] == 0
    assert b["w2v"]["compile_ms"] > 0
    assert c["w2v"]["retraces"] == 2
    assert ctb.retrace_violations(b, c) == [("w2v", 0.0, 2.0)]
    # floor 1: a single late retrace is tolerated...
    assert ctb.retrace_violations(b, {"w2v": {"retraces": 1.0}}) == []
    # ...and a costs-off candidate is skipped, never blocked
    assert ctb.retrace_violations(b, {"w2v": {}}) == []

    assert ctb.main([base, cand]) == 1
    assert "RETRACE BUDGET EXCEEDED" in capsys.readouterr().out
    assert ctb.main([base, base]) == 0


# -- triggered profiler windows --------------------------------------------

def test_fleet_trigger_file_drives_a_capture(tmp_path):
    """request_profile -> trigger file -> session capture, replayed
    exactly once per monotonic id."""
    fleet = str(tmp_path / "fleet")
    req = obs_profiler.request_profile(fleet, steps=1)
    assert req["id"] == 1
    assert obs_profiler.request_profile(fleet, steps=1)["id"] == 2

    obs.set_enabled(True)
    sess = obs_profiler.ProfileSession(
        profile_dir=str(tmp_path / "prof"), fleet_dir=fleet)
    f = jax.jit(lambda x: x + 1.0)
    sess.on_step()                 # polls, parks, starts the capture
    f(jnp.ones((4,), jnp.float32))
    sess.on_step()                 # window of 1 consumed -> stop
    assert len(sess.captures) == 1
    assert sess.captures[0]["reason"] == "trigger:2"
    assert os.path.exists(os.path.join(sess.captures[0]["run_dir"],
                                       "profile_summary.json"))
    # same id again: never replayed
    sess._last_poll = 0.0
    sess.on_step()
    sess.on_step()
    assert len(sess.captures) == 1


# (plane, line, [(name, start_ns, end_ns), ...])
_TIMELINE = [
    ("/device:TPU:0", "XLA Modules", [
        ("jit_step(704314026086775491)", 100, 500),
        ("jit_other(9)", 600, 700)]),
    ("/device:TPU:0", "XLA Ops", [
        # the device names an op by its HLO text
        ("%while.1 = (s32[], f32[4,4]{1,0}) while(%fusion.25), cond", 100,
         500),                               # self: 400 - 100 - 100 - 50
        ("%fusion.2 = f32[4,4]{1,0} fusion(%copy.1), kind=kLoop", 120, 220),
        ("%sort.3 = f32[4,4]{1,0} sort(%gte.1)", 250, 350),
        ("%copy.5 = f32[8,4]{0,1} copy(%state)", 400, 450),  # not in the map
        # the same instruction name in another program: not step's pull
        ("%fusion.2 = u32[2]{0} fusion(%key)", 600, 700)]),
    ("/host:CPU", "main", [
        ("train_setup", 0, 50), ("PjitFunction(step)", 55, 95),
        ("dispatch", 60, 90), ("dispatch", 510, 530)]),
    ("/host:CPU", "producer", [("render", 0, 800)]),
]
_STEP_MAP = {"jit_step": {
    "module": "jit_step", "instructions": 3, "unscoped": 0,
    "phase": {"while.1": "dedup", "fusion.2": "pull", "sort.3": "dedup"}}}


def _xspace_text(timeline):
    names = sorted({e[0] for _, _, evs in timeline for e in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    planes = {}
    for plane, line, evs in timeline:
        planes.setdefault(plane, []).append((line, evs))
    text = ""
    for p, (plane, lines) in enumerate(planes.items()):
        text += f'planes {{ id: {p} name: "{plane}"\n'
        for i, (line, evs) in enumerate(lines):
            text += f'  lines {{ id: {i} name: "{line}" timestamp_ns: 0\n'
            for name, s, e in evs:
                text += (f"    events {{ metadata_id: {ids[name]} "
                         f"offset_ps: {s * 1000} "
                         f"duration_ps: {(e - s) * 1000} }}\n")
            text += "  }\n"
        for name, i in ids.items():
            text += (f"  event_metadata {{ key: {i} value {{ id: {i} "
                     f'name: "{name}" }} }}\n')
        text += "}\n"
    return text


def test_parse_trace_dir_attributes_phases(tmp_path):
    """The capture's .xplane.pb reduced by self time through the phase
    map: nested time is counted once, an instruction name is looked up
    in the program that ran it, and the phases sum to busy time."""
    from jax.profiler import ProfileData
    assert obs_profiler.self_times(
        [(0, 10, "a"), (2, 5, "b"), (3, 4, "c"), (6, 8, "d")]) == [
        ["a", 5, 0], ["b", 2, 2], ["c", 1, 3], ["d", 2, 6]]

    text = _xspace_text(_TIMELINE)
    part = obs_profiler.reduce_profile(ProfileData.from_text_proto(text),
                                       _STEP_MAP)
    assert part["devices"] == 1 and part["unmatched"] == 1

    root = tmp_path / "cap" / "plugins" / "profile" / "t0"
    root.mkdir(parents=True)
    (root / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    s = obs_profiler.parse_trace_dir(str(tmp_path / "cap"), _STEP_MAP)
    assert s["schema"] == "smtpu-profile/2"
    assert s["files"] == 1 and s["events"] == 5 + 4
    ms = 1e-6                               # the timeline is in ns
    assert s["device_ms"]["dedup"] == pytest.approx((150 + 100) * ms)
    assert s["device_ms"]["pull"] == pytest.approx(100 * ms)
    assert s["device_ms"]["unscoped"] == pytest.approx((50 + 100) * ms)
    assert "other" not in s["device_ms"]
    # every device instant lands in exactly one phase
    assert sum(s["device_ms"].values()) == pytest.approx(500 * ms)
    assert s["busy_ms"] == pytest.approx(500 * ms)
    assert s["modules_ms"] == pytest.approx(
        {"jit_step": 400 * ms, "jit_other": 100 * ms})
    assert s["module_runs"] == {"jit_step": 1, "jit_other": 1}
    # host spans by name, over every host thread; other events ignored
    assert s["host_ms"] == pytest.approx(
        {"train_setup": 50 * ms, "dispatch": 50 * ms, "render": 800 * ms})
    assert s["skew_ms"]["dispatch"] == pytest.approx(50 * ms)
    assert s["skew_ms"]["pull"] == pytest.approx(-100 * ms)
    # no map: the same busy time, all of it under no scope
    bare = obs_profiler.parse_trace_dir(str(tmp_path / "cap"), {})
    assert bare["device_ms"] == pytest.approx({"unscoped": 500 * ms})
    # an empty directory reduces to an empty summary
    empty = obs_profiler.parse_trace_dir(str(tmp_path / "none"), {})
    assert empty["files"] == 0 and empty["device_ms"] == {}


# -- the contract the default rides on -------------------------------------

@pytest.mark.parametrize("transfer", [
    "xla",
    # tpu/hybrid re-prove the same observe-only contract through
    # heavier transfers (~14s of compile); tier-1's wall budget keeps
    # them in the slow lane — the xla representative keeps the
    # catalog-off contract in tier-1
    pytest.param("tpu", marks=pytest.mark.slow),
    pytest.param("hybrid", marks=pytest.mark.slow),
])
def test_costs_off_bit_identical(transfer, devices8, tmp_path):
    """Arming the catalog only OBSERVES the jit handles (the wrapped
    jit is always the callee; analysis is lower()-side) — so ON vs OFF
    must produce identical per-iteration losses AND bit-identical final
    parameters on every jit-stepped backend."""
    corp = _corpus()
    l_off, p_off, _ = _train_final(_cfg(transfer), corp, niters=2)
    assert obs_costs.get_catalog().entries() == {}   # default: nothing

    obs.reset_for_tests()
    cat_path = str(tmp_path / f"cat_{transfer}.json")
    l_on, p_on, _ = _train_final(
        _cfg(transfer, path=str(tmp_path / f"tel_{transfer}.jsonl"),
             obs_extra={"costs": 1, "costs_path": cat_path,
                        "costs_memory": 0}), corp, niters=2)
    assert l_off == l_on
    assert set(p_off) == set(p_on)
    for k in p_off:
        np.testing.assert_array_equal(p_off[k], p_on[k],
                                      err_msg=f"{transfer}/{k}")
    # ...and the catalog actually ran
    doc = json.load(open(cat_path))
    assert any(v["compiles"] > 0 for v in doc["fns"].values())
