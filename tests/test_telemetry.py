"""Telemetry plane tests (ISSUE 6): registry semantics, thread safety,
StepRecorder ring/JSONL behavior, the off-by-default overhead contract,
cross-backend traffic mirror consistency, and the end-to-end w2v smoke
run through ``[worker] telemetry: 1``."""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from swiftmpi_tpu import obs
from swiftmpi_tpu.obs.recorder import StepRecorder
from swiftmpi_tpu.obs.registry import (MetricsRegistry, parse_series_key,
                                       quantile_from_buckets, series_key)

SCRIPTS = os.path.join(os.path.dirname(__file__), os.pardir, "scripts")


def _scripts_on_path():
    if SCRIPTS not in sys.path:
        sys.path.insert(0, SCRIPTS)


# -- registry basics ------------------------------------------------------

def test_series_key_roundtrip():
    key = series_key("transfer/wire_bytes", {"backend": "tpu", "a": "b"})
    assert key == "transfer/wire_bytes{a=b,backend=tpu}"   # sorted labels
    name, labels = parse_series_key(key)
    assert name == "transfer/wire_bytes"
    assert labels == {"backend": "tpu", "a": "b"}
    assert parse_series_key("plain") == ("plain", {})


def test_counter_monotonic_and_set_total():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("x")
    c.inc(3)
    c.inc(2.5)
    assert c.value == 5.5
    c.set_total(10.0)         # external cumulative total: jumps forward
    assert c.value == 10.0
    c.set_total(4.0)          # ...but never backwards
    assert c.value == 10.0
    # same (name, labels) -> same handle
    assert reg.counter("x") is c
    assert reg.counter("x", k="v") is not c


def test_gauge_and_histogram():
    reg = MetricsRegistry(enabled=True)
    g = reg.gauge("depth")
    g.set(3)
    g.set(1)
    assert g.value == 1.0      # last write wins
    h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0, 100.0):   # 100 -> overflow bucket
        h.observe(v)
    assert h.count == 5 and h.counts == [1, 2, 1, 1]
    # overflow clamps to the top finite edge
    assert reg.quantile("lat", 0.99) == pytest.approx(4.0)
    assert 1.0 <= reg.quantile("lat", 0.5) <= 2.0


def test_quantile_from_buckets_interpolates():
    bounds = (10.0, 20.0)
    assert quantile_from_buckets(bounds, [0, 0, 0], 0.5) == 0.0
    # all mass in the (10, 20] bucket: median interpolates inside it
    q = quantile_from_buckets(bounds, [0, 100, 0], 0.5)
    assert 10.0 < q <= 20.0


def test_disabled_registry_writes_are_noops():
    reg = MetricsRegistry(enabled=False)
    c, g = reg.counter("c"), reg.gauge("g")
    h = reg.histogram("h")
    c.inc(5)
    g.set(7)
    h.observe(1.0)
    assert c.value == 0.0 and g.value == 0.0 and h.count == 0


def test_delta_reports_only_moved_series():
    reg = MetricsRegistry(enabled=True)
    a, b = reg.counter("a"), reg.counter("b")
    a.inc(1)
    b.inc(1)
    prev = reg.snapshot()
    a.inc(4)
    d = MetricsRegistry.delta(prev, reg.snapshot())
    assert d["counters"] == {"a": 4.0}        # b did not move
    assert "b" not in d["hists"]


# -- thread safety --------------------------------------------------------

def test_concurrent_producer_consumer_writes():
    """The input pipeline's producer thread and the training loop write
    the same registry concurrently; totals must be exact (no lost
    updates) and snapshots internally consistent."""
    reg = MetricsRegistry(enabled=True)
    N, THREADS = 5000, 4
    snapshots = []
    stop = threading.Event()

    def produce(i):
        c = reg.counter("prod", t=str(i))
        shared = reg.counter("shared")
        h = reg.histogram("lat")
        for _ in range(N):
            c.inc()
            shared.inc()
            h.observe(1.0)

    def consume():
        while not stop.is_set():
            snapshots.append(reg.snapshot())

    threads = [threading.Thread(target=produce, args=(i,))
               for i in range(THREADS)]
    reader = threading.Thread(target=consume)
    reader.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    reader.join()
    assert reg.counter("shared").value == N * THREADS
    for i in range(THREADS):
        assert reg.counter("prod", t=str(i)).value == N
    assert reg.histogram("lat").count == N * THREADS
    # counters never run backwards across consumer snapshots
    last = 0.0
    for s in snapshots:
        v = s["counters"].get("shared", 0.0)
        assert v >= last
        last = v


# -- StepRecorder ---------------------------------------------------------

def test_recorder_ring_bounds_long_run():
    reg = MetricsRegistry(enabled=True)
    rec = StepRecorder(reg, path=None, ring=16)
    c = reg.counter("k")
    for i in range(10_000):
        c.inc()
        rec.on_steps(1)
    assert rec.steps_recorded == 10_000
    recs = rec.records()
    assert len(recs) == 16                    # bounded, not O(steps)
    assert recs[-1]["step"] == 10_000
    assert recs[0]["step"] == 10_000 - 15


def test_recorder_every_thinning_and_close_tail():
    reg = MetricsRegistry(enabled=True)
    rec = StepRecorder(reg, path=None, ring=64, every=10)
    for _ in range(95):
        rec.on_steps(1)
    assert len(rec.records()) == 9            # 9 full cadences
    rec.close()                               # tail 5 steps recorded
    recs = rec.records()
    assert len(recs) == 10 and recs[-1]["steps"] == 5
    assert rec.summary["steps"] == 95


def test_recorder_validates_knobs():
    reg = MetricsRegistry(enabled=True)
    with pytest.raises(ValueError):
        StepRecorder(reg, ring=0)
    with pytest.raises(ValueError):
        StepRecorder(reg, every=0)


def test_recorder_jsonl_schema(tmp_path):
    reg = MetricsRegistry(enabled=True)
    path = str(tmp_path / "telemetry.jsonl")
    rec = StepRecorder(reg, path=path, run="t", flush_every=2,
                       meta={"extra": "yes"})
    c = reg.counter("transfer/wire_bytes", backend="tpu")
    h = reg.histogram("phase_ms", phase="dispatch")
    for i in range(5):
        c.inc(100)
        h.observe(1.0 + i)
        rec.on_steps(1)
    rec.close()
    rec.close()                               # idempotent
    lines = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert [r["kind"] for r in lines] == \
        ["meta"] + ["step"] * 5 + ["summary"]
    meta = lines[0]
    assert meta["schema"] == obs.SCHEMA and meta["extra"] == "yes"
    assert meta["pid"] == os.getpid()
    hkey = "phase_ms{phase=dispatch}"
    for n, r in enumerate(lines[1:6], start=1):
        assert r["v"] == obs.SCHEMA_V and r["step"] == n
        assert r["counters"]["transfer/wire_bytes{backend=tpu}"] == 100.0
        # bucket bounds ride along only the first time a series appears
        assert ("bounds" in r["hists"][hkey]) == (n == 1)
    summary = lines[-1]
    assert summary["steps"] == 5
    assert summary["counters"]["transfer/wire_bytes{backend=tpu}"] == 500.0
    q = summary["quantiles"][hkey]
    assert q["n"] == 5 and q["p50"] <= q["p95"] <= q["p99"]


def test_recorder_sampler_bridges_external_totals():
    """Instruments with private cumulative state (the Throughput meter)
    publish through a sampler + set_total — deltas must behave as if
    the series were native."""
    reg = MetricsRegistry(enabled=True)
    rec = StepRecorder(reg, path=None, ring=8)
    total = {"v": 0.0}
    rec.add_sampler(
        lambda r: r.counter("train/host_stall_ms_total").set_total(
            total["v"]))
    total["v"] = 3.0
    rec.on_steps(1)
    total["v"] = 7.5
    rec.on_steps(1)
    recs = rec.records()
    assert recs[0]["counters"]["train/host_stall_ms_total"] == 3.0
    assert recs[1]["counters"]["train/host_stall_ms_total"] == 4.5


def test_identity_follows_env(monkeypatch):
    from swiftmpi_tpu.cluster.bootstrap import ENV_PROCESS_ID
    from swiftmpi_tpu.obs.identity import process_ident, process_rank
    monkeypatch.delenv(ENV_PROCESS_ID, raising=False)
    assert process_rank() is None
    assert process_ident() == f"p{os.getpid()}"
    monkeypatch.setenv(ENV_PROCESS_ID, "3")
    assert process_rank() == 3 and process_ident() == "r3"
    reg = MetricsRegistry(enabled=True)
    rec = StepRecorder(reg, path=None)
    rec.on_steps(1)
    assert rec.records()[0]["rank"] == 3
    assert rec.records()[0]["ident"] == "r3"


# -- spans and overhead ---------------------------------------------------

def test_span_disabled_is_shared_noop():
    assert not obs.get_registry().enabled
    # one shared singleton: no allocation, no state, per call site
    assert obs.span("render") is obs.span("dispatch")


def test_span_enabled_feeds_phase_histogram():
    obs.set_enabled(True)
    with obs.span("unit_test_phase"):
        time.sleep(0.002)
    reg = obs.get_registry()
    h = reg.histogram("phase_ms", phase="unit_test_phase")
    assert h.count == 1
    assert 1.0 <= reg.quantile("phase_ms{phase=unit_test_phase}", 0.5) \
        <= 200.0


def test_overhead_disabled_near_zero():
    """Telemetry off must cost one branch per instrument write — the
    whole plane rides in every hot path on this promise."""
    reg = obs.get_registry()
    assert not reg.enabled
    c = reg.counter("hot/path")
    N = 100_000
    t0 = time.perf_counter()
    for _ in range(N):
        c.inc()
    per_inc = (time.perf_counter() - t0) / N
    t0 = time.perf_counter()
    for _ in range(N):
        obs.span("dispatch")
    per_span = (time.perf_counter() - t0) / N
    assert c.value == 0.0
    # generous CI bound; the real cost is ~100ns (attribute check + ret)
    assert per_inc < 5e-6, f"disabled inc cost {per_inc * 1e9:.0f}ns"
    assert per_span < 5e-6, f"disabled span cost {per_span * 1e9:.0f}ns"


def test_overhead_enabled_bounded():
    """Telemetry on: a counter write is one small lock, and a full
    per-step record over a realistically-sized registry stays far under
    the cheapest measured pipeline step (~tens of ms on the CPU bench
    cells) — recording per step must never dominate a step."""
    obs.set_enabled(True)
    reg = obs.get_registry()
    c = reg.counter("hot/path")
    N = 50_000
    t0 = time.perf_counter()
    for _ in range(N):
        c.inc()
    per_inc = (time.perf_counter() - t0) / N
    assert per_inc < 5e-5, f"enabled inc cost {per_inc * 1e9:.0f}ns"
    # ~40 series, like a real run (4 backends x wire keys + phases)
    for i in range(30):
        reg.counter(f"s{i}", backend="tpu").inc(i)
    for p in ("render", "h2d", "dispatch", "input_wait"):
        reg.histogram("phase_ms", phase=p).observe(1.0)
    rec = StepRecorder(reg, path=None, ring=128)
    reps = 200
    t0 = time.perf_counter()
    for _ in range(reps):
        reg.counter("hot/path").inc()
        rec.on_steps(1)
    per_record = (time.perf_counter() - t0) / reps
    assert per_record < 5e-3, \
        f"per-step record cost {per_record * 1e3:.2f}ms"


# -- cross-backend traffic mirror -----------------------------------------

MIRRORED_WIRE_KEYS = ("wire_bytes", "dispatches", "window_sparse",
                      "window_dense", "coalesced_rows_in",
                      "coalesced_rows_out", "routed_rows", "hot_rows",
                      "psum_bytes", "overflow_dropped")


def _registry_backend_sum(reg, key):
    """Sum ``transfer/<key>`` across backend labels (hybrid splits its
    ledger between its own label and its tail backend's)."""
    total = 0.0
    for skey in reg.series_keys():
        name, _ = parse_series_key(skey)
        if name == "transfer/" + key:
            total += reg._counters[skey].value
    return total


@pytest.mark.parametrize("backend_name",
                         ["local", "xla", "tpu", "hybrid"])
def test_traffic_mirror_consistency(backend_name, devices8):
    """traffic() totals and the telemetry registry mirror must agree on
    every backend, and both must be monotonic across pushes — the
    documented reset contract (no reset; readers take deltas)."""
    from swiftmpi_tpu.cluster import SHARD_AXIS, ps_mesh
    from swiftmpi_tpu.parameter import KeyIndex, SparseTable, w2v_access
    from swiftmpi_tpu.transfer.hybrid import HybridTransfer
    from swiftmpi_tpu.transfer.local import LocalTransfer
    from swiftmpi_tpu.transfer.tpu import TpuTransfer
    from swiftmpi_tpu.transfer.xla import XlaTransfer

    obs.set_enabled(True)
    reg = obs.get_registry()
    mesh = ps_mesh()
    access = w2v_access(learning_rate=0.3, len_vec=8)
    ki = KeyIndex(num_shards=8, capacity_per_shard=32)
    table = SparseTable(access, ki, mesh=mesh, axis=SHARD_AXIS)
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 10_000, size=64).astype(np.uint64)
    slots = ki.lookup(keys)
    grads = {f: rng.normal(size=(64, 8)).astype(np.float32)
             for f in access.grad_fields}
    backend = {"local": LocalTransfer, "xla": XlaTransfer,
               "tpu": lambda: TpuTransfer(mesh),
               "hybrid": lambda: HybridTransfer(mesh)}[backend_name]()
    backend.count_traffic = True
    state = ({f: np.asarray(v) for f, v in table.state.items()}
             if backend_name == "local" else table.state)
    state = backend.push(state, slots, grads, access)
    tr1 = backend.traffic()
    assert tr1["wire_bytes"] > 0 and tr1["dispatches"] > 0
    state = backend.push(state, slots, grads, access)
    tr2 = backend.traffic()
    for k in tr1:
        assert tr2[k] >= tr1[k], f"{k} went backwards"     # monotonic
    assert tr2["wire_bytes"] == 2 * tr1["wire_bytes"]
    # registry mirror agrees exactly with the ledger totals
    for k in MIRRORED_WIRE_KEYS:
        if k in tr2:
            assert _registry_backend_sum(reg, k) == tr2[k], k


def test_traffic_mirror_survives_registry_reset(devices8):
    """Writers cache instrument handles; a reset_for_tests swap must
    redirect them to the new registry (identity re-check), not strand
    writes in the discarded one."""
    from swiftmpi_tpu.cluster import SHARD_AXIS, ps_mesh
    from swiftmpi_tpu.parameter import KeyIndex, SparseTable, w2v_access
    from swiftmpi_tpu.transfer.xla import XlaTransfer

    obs.set_enabled(True)
    access = w2v_access(learning_rate=0.3, len_vec=8)
    ki = KeyIndex(num_shards=8, capacity_per_shard=32)
    table = SparseTable(access, ki, mesh=ps_mesh(), axis=SHARD_AXIS)
    slots = ki.lookup(np.arange(16, dtype=np.uint64))
    grads = {f: np.ones((16, 8), np.float32) for f in access.grad_fields}
    backend = XlaTransfer()
    backend.count_traffic = True
    state = backend.push(table.state, slots, grads, access)
    t1 = backend.traffic()
    reg2 = obs.reset_for_tests()
    obs.set_enabled(True)
    backend.push(state, slots, grads, access)
    backend.traffic()
    assert _registry_backend_sum(reg2, "wire_bytes") == t1["wire_bytes"]


# -- end-to-end smoke: w2v run emits schema-valid telemetry ----------------

def test_w2v_run_emits_valid_telemetry(tmp_path, devices8):
    from swiftmpi_tpu.data.text import synthetic_corpus
    from swiftmpi_tpu.models.word2vec import Word2Vec
    from swiftmpi_tpu.utils import ConfigParser

    path = str(tmp_path / "telemetry.jsonl")
    cfg = ConfigParser().update({
        "cluster": {"transfer": "xla"},
        "word2vec": {"len_vec": 16, "window": 2, "negative": 5,
                     "sample": -1, "learning_rate": 0.05,
                     "min_sentence_length": 2},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 512, "telemetry": 1,
                   "telemetry_path": path, "telemetry_flush": 1},
    })
    corpus = synthetic_corpus(40, vocab_size=60, length=14, seed=8)
    model = Word2Vec(config=cfg)
    losses = model.train(corpus, niters=3, batch_size=64)
    assert len(losses) == 3
    # train() owns and closes the recorder it configured
    assert obs.get_recorder() is None

    lines = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert lines[0]["kind"] == "meta"
    assert lines[0]["schema"] == obs.SCHEMA
    assert lines[0]["run"] == "word2vec"
    assert lines[-1]["kind"] == "summary"
    steps = [r for r in lines if r["kind"] == "step"]
    assert steps and sum(r["steps"] for r in steps) \
        == lines[-1]["steps"] > 0
    # the dispatch span must have fired at least once per step
    assert any("phase_ms{phase=dispatch}" in (r.get("hists") or {})
               for r in steps)
    # train samplers publish the throughput meter's split
    assert "train/host_stall_ms_total" in lines[-1]["counters"]

    # the run analyzer parses it and finds the dispatch phase
    _scripts_on_path()
    import telemetry_report
    rep = telemetry_report.report(telemetry_report.load(path))
    assert any(r["phase"] == "dispatch" for r in rep["phases"])
    assert rep["traffic"]["steps"] == lines[-1]["steps"]

    # ...and the traffic-budget gate accepts it as a cell source:
    # a run gated against itself is within any budget
    import check_traffic_budget
    cells = check_traffic_budget.load_cells(path)
    assert "word2vec" in cells
    assert check_traffic_budget.main([path, path]) == 0


def test_overhead_bounded_on_pipeline_shape(tmp_path, devices8):
    """Acceptance: telemetry-on overhead measured against the pipelined
    train loop's own step time.  A real `[worker] pipeline` w2v run with
    telemetry on gives the per-step wall time AND a registry populated
    with that run's actual series; re-recording over that registry must
    cost well under a step."""
    from swiftmpi_tpu.data.text import synthetic_corpus
    from swiftmpi_tpu.models.word2vec import Word2Vec
    from swiftmpi_tpu.utils import ConfigParser

    path = str(tmp_path / "telemetry.jsonl")
    cfg = ConfigParser().update({
        "cluster": {"server_num": 2, "transfer": "xla"},
        "word2vec": {"len_vec": 16, "window": 2, "negative": 5,
                     "sample": -1, "learning_rate": 0.05,
                     "min_sentence_length": 2},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 512, "inner_steps": 2, "pipeline": 2,
                   "telemetry": 1, "telemetry_path": path},
    })
    corpus = synthetic_corpus(40, vocab_size=60, length=14, seed=8)
    model = Word2Vec(config=cfg)
    t0 = time.perf_counter()
    model.train(corpus, niters=3, batch_size=64)
    elapsed = time.perf_counter() - t0
    lines = [json.loads(ln) for ln in open(path) if ln.strip()]
    steps = lines[-1]["steps"]
    assert steps > 0
    # the pipeline spans fired: the producer recorded render + h2d
    hist_keys = set()
    for r in lines:
        hist_keys |= set(r.get("hists") or {})
    hist_keys |= set(lines[-1].get("quantiles") or {})
    assert "phase_ms{phase=render}" in hist_keys
    assert "phase_ms{phase=h2d}" in hist_keys
    per_step_wall = elapsed / steps
    # re-record over the run's own (still-enabled, fully-populated)
    # registry: per-record cost must be a small fraction of a step
    reg = obs.get_registry()
    rec = StepRecorder(reg, path=None, ring=64)
    reps = 50
    t0 = time.perf_counter()
    for _ in range(reps):
        reg.counter("transfer/wire_bytes", backend="xla").inc()
        rec.on_steps(1)
    per_record = (time.perf_counter() - t0) / reps
    assert per_record < 0.1 * per_step_wall, \
        (f"telemetry record {per_record * 1e3:.3f}ms vs step "
         f"{per_step_wall * 1e3:.1f}ms")


def test_configure_off_by_default(tmp_path):
    from swiftmpi_tpu.utils import ConfigParser
    cfg = ConfigParser().update({"worker": {"minibatch": 64}})
    assert obs.configure(cfg) is None
    assert not obs.get_registry().enabled


# -- 4-way wire-format decision series in the run analyzer -----------------

def _fmt_doc():
    """Synthetic analyzer doc: two steps whose counters carry the
    labeled transfer/window_fmt series next to the legacy 2-way
    counters (sparse_q windows bump BOTH, by design)."""
    steps = [
        {"kind": "step", "step": 1, "steps": 1, "counters": {
            "transfer/window_fmt{backend=tpu,fmt=q}": 2.0,
            "transfer/window_sparse{backend=tpu}": 2.0,
            "transfer/wire_bytes{backend=tpu}": 700.0}},
        {"kind": "step", "step": 2, "steps": 1, "counters": {
            "transfer/window_fmt{backend=tpu,fmt=bitmap}": 1.0,
            "transfer/window_sparse{backend=tpu}": 1.0,
            "transfer/wire_bytes{backend=tpu}": 300.0}},
    ]
    return {"meta": {"run": "fmtrun"}, "steps": steps, "events": [],
            "summary": None}


def test_traffic_summary_folds_window_fmt_labels():
    """The labeled decision counter must fold into window_fmt_<fmt>
    keys per backend — four series, four keys, no dict collision."""
    _scripts_on_path()
    import telemetry_report
    t = telemetry_report.traffic_summary(_fmt_doc())
    tpu = t["transfer"]["tpu"]
    assert tpu["window_fmt_q"] == 2.0
    assert tpu["window_fmt_bitmap"] == 1.0
    assert "window_fmt" not in tpu          # no overwritten shared key
    assert tpu["window_sparse"] == 3.0      # legacy series intact


@pytest.mark.parametrize("finished", [True, False],
                         ids=["summary_line", "crashed_run"])
def test_traffic_summary_carries_train_gauges(finished):
    """The ``train/`` gauges' last values print with the ``train/``
    totals, label kept (which slot-lookup branch the step took)."""
    _scripts_on_path()
    import telemetry_report
    key = "train/sampler_slot_lookups{mode=per_draw}"
    doc = _fmt_doc()
    doc["steps"][0]["gauges"] = {key: 80.0, "pipeline/queue_depth": 2.0}
    if finished:
        doc["summary"] = {"steps": 2, "gauges": {key: 80.0}, "counters": {
            "train/host_stall_ms_total": 3.0}}
    t = telemetry_report.traffic_summary(doc)
    assert t["train"]["sampler_slot_lookups{mode=per_draw}"] == 80.0
    assert ("host_stall_ms_total" in t["train"]) == finished
    assert "queue_depth" not in str(t["train"])


def test_wire_timeline_prefers_fmt_labels():
    """Steps carrying the fmt-labeled series are labeled by the actual
    4-way decision, not 'mixed' with the coarser legacy counter."""
    _scripts_on_path()
    import telemetry_report
    runs = telemetry_report.wire_timeline(_fmt_doc())
    assert [r["decision"] for r in runs] == ["q", "bitmap"]


def test_budget_gate_decision_mix_floor():
    """A cell claiming wire_quant is armed but whose decision mix never
    picked an encoded format must fail the gate (exit 1); a mix with
    any q/bitmap share passes."""
    _scripts_on_path()
    import check_traffic_budget as ctb
    dead = {"w2v_1m_qwire": {"wire_quant": "int8", "window_fmt_q": 0,
                             "window_fmt_sparse": 40.0}}
    assert ctb.decision_mix_violations(dead) \
        == [("w2v_1m_qwire", "int8", 40.0)]
    live = {"w2v_1m_qwire": {"wire_quant": "int8", "window_fmt_q": 30.0,
                             "window_fmt_sparse": 10.0}}
    assert ctb.decision_mix_violations(live) == []
    off = {"w2v_1m_window": {"window_fmt_sparse": 40.0}}
    assert ctb.decision_mix_violations(off) == []


def test_traffic_summary_folds_collective_labels():
    """The kind-labeled collective decision counter folds into the
    ledger key names (collective_psum / collective_sparse_ar) per
    backend, next to the window_fmt folding it mirrors."""
    _scripts_on_path()
    import telemetry_report
    doc = _fmt_doc()
    doc["steps"][0]["counters"][
        "transfer/collective{backend=hybrid,kind=sparse_ar}"] = 2.0
    doc["steps"][1]["counters"][
        "transfer/collective{backend=hybrid,kind=psum}"] = 1.0
    doc["steps"][1]["counters"][
        "transfer/hot_psum_bytes_saved{backend=hybrid}"] = 4096.0
    t = telemetry_report.traffic_summary(doc)
    hyb = t["transfer"]["hybrid"]
    assert hyb["collective_sparse_ar"] == 2.0
    assert hyb["collective_psum"] == 1.0
    assert "collective" not in hyb          # no overwritten shared key
    assert hyb["hot_psum_bytes_saved"] == 4096.0


def test_budget_gate_collective_mix_floor():
    """A cell that armed the collective ladder (auto or pinned) and
    booked decisions yet never picked sparse_allreduce fails the gate;
    any sparse_ar share passes, and collective=psum (or absent) is
    exempt — the ladder was never armed."""
    _scripts_on_path()
    import check_traffic_budget as ctb
    dead = {"w2v_1m_sparsear": {"collective": "auto",
                                "collective_psum": 12.0,
                                "collective_sparse_ar": 0}}
    assert ctb.collective_mix_violations(dead) \
        == [("w2v_1m_sparsear", "auto", 12.0)]
    live = {"w2v_1m_sparsear": {"collective": "auto",
                                "collective_psum": 4.0,
                                "collective_sparse_ar": 8.0}}
    assert ctb.collective_mix_violations(live) == []
    off = {"w2v_1m_hybrid": {"collective": "psum",
                             "collective_psum": 12.0},
           "w2v_1m_window": {"window_fmt_sparse": 40.0}}
    assert ctb.collective_mix_violations(off) == []
    # hot_psum_bytes_per_step is a gated lower-is-better traffic metric
    assert "hot_psum_bytes_per_step" in ctb.TRAFFIC_METRICS
    grown = {"c": {"hot_psum_bytes_per_step": 8000.0}}
    base = {"c": {"hot_psum_bytes_per_step": 2000.0}}
    reg = ctb.compare(base, grown, 0.1)
    assert [(r[0], r[1]) for r in reg] == [("c",
                                            "hot_psum_bytes_per_step")]


def test_budget_gate_aggregates_fmt_cells(tmp_path):
    """load_telemetry_cells surfaces the folded window_fmt_* totals as
    cell detail so the decision-mix floor sees live-run JSONL too."""
    _scripts_on_path()
    import check_traffic_budget as ctb
    path = str(tmp_path / "t.jsonl")
    doc = _fmt_doc()
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "meta", "schema": obs.SCHEMA,
                            "run": "fmtrun"}) + "\n")
        for rec in doc["steps"]:
            f.write(json.dumps(rec) + "\n")
    cells = ctb.load_cells(path)
    assert cells["fmtrun"]["window_fmt_q"] == 2.0
    assert cells["fmtrun"]["window_fmt_bitmap"] == 1.0
    assert cells["fmtrun"]["window_sparse"] == 3.0


# -- pair counters (ISSUE 26) ----------------------------------------------

class _RecordingBatcher:
    """Forwards a CBOWBatcher's batches and keeps what was fed: of a
    span batch (what a CBOW model asks for), the pairs of its per-pair
    expansion and the span's positions."""

    def __init__(self, inner):
        self.inner, self.vocab = inner, inner.vocab
        self.valid, self.grid, self.span_rows = [], 0, []

    def _fed(self, ctx_mask):
        self.valid.append(int(np.asarray(ctx_mask).sum()))
        self.grid += ctx_mask.size

    def epoch(self, batch_size):
        for batch in self.inner.epoch(batch_size):
            self._fed(batch.ctx_mask)
            yield batch

    def epoch_stencil(self, batch_size):
        from swiftmpi_tpu.data.text import stencil_to_cbow

        for batch in self.inner.epoch_stencil(batch_size):
            self._fed(stencil_to_cbow(batch, self.inner.window).ctx_mask)
            self.span_rows.append(batch.span)
            yield batch


def _pairs_run(sg, worker, telemetry, tmp_path, extra=None):
    from swiftmpi_tpu.data.text import (CBOWBatcher, build_vocab,
                                        synthetic_corpus)
    from swiftmpi_tpu.models.word2vec import Word2Vec
    from swiftmpi_tpu.utils import ConfigParser

    cfg = ConfigParser().update({
        "cluster": {"transfer": "xla"},
        "word2vec": {"len_vec": 8, "window": 3, "negative": 2, "sg": sg,
                     "sample": -1, "learning_rate": 0.05},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 512, "telemetry": telemetry,
                   "telemetry_path": str(tmp_path / "telemetry.jsonl"),
                   **worker},
    })
    cfg.update(extra or {})
    corpus = synthetic_corpus(30, vocab_size=50, length=12, seed=4)
    model = Word2Vec(config=cfg)
    model.build_from_vocab(build_vocab(corpus))
    batcher = _RecordingBatcher(CBOWBatcher(corpus, model.vocab,
                                            model.window))
    model.train(batcher=batcher, niters=2, batch_size=32)
    return model, batcher


@pytest.mark.parametrize("worker", [{}, {"inner_steps": 3},
                                    {"pipeline": 2}],
                         ids=["single", "fused", "pipelined"])
@pytest.mark.parametrize("sg", [0, 1], ids=["cbow", "sg"])
def test_pair_counters_equal_the_batches_fed(sg, worker, tmp_path,
                                             devices8):
    """``pairs_per_step`` / ``pair_fill_share`` and ``train/pairs`` are
    the host batches' ``ctx_mask.sum()`` against their ``(B, 2W)`` grid,
    whichever way the loop feeds the step — and whichever way the batch
    is rendered: a CBOW model takes spans, whose pairs are counted from
    the positions (the grid is never built) and equal the expansion's;
    ``span_rows_per_step`` is what its step pulls and pushes instead."""
    model, fed = _pairs_run(sg, worker, 1, tmp_path)
    m = model.train_metrics
    assert bool(model.stencil) == (not sg) == bool(fed.span_rows)
    if sg:
        assert "span_rows_per_step" not in m
    else:
        assert m["span_rows_per_step"] == np.mean(fed.span_rows) == 128
    assert len(fed.valid) > 4 and 0 < sum(fed.valid) < fed.grid
    assert m["pairs_per_step"] == pytest.approx(
        sum(fed.valid) / len(fed.valid), rel=1e-12)
    assert m["pair_fill_share"] == pytest.approx(
        100.0 * sum(fed.valid) / fed.grid, rel=1e-12)
    reg = obs.get_registry()
    assert reg.counter("train/pairs", kind="valid").value == sum(fed.valid)
    assert reg.counter("train/pairs", kind="grid").value == fed.grid


@pytest.mark.parametrize("sg", [0, 1], ids=["cbow", "sg"])
def test_rows_written_counts_each_push_s_distinct_rows(sg, monkeypatch,
                                                       tmp_path, devices8):
    """``rows_written_per_step``: every push of a step adds its
    distinct valid slots times the fields it touches (a parameter and its
    AdaGrad accumulator), counted on the device and fetched with the loss;
    here against the slots each push was handed, taken out by a callback.
    A CBOW step on the 8-device mesh runs split over the table's axis
    (ISSUE 43): every chip then makes the push with its share of the
    slots, and the rows are the distinct slots of the shares together."""
    import jax

    from swiftmpi_tpu.transfer.xla import XlaTransfer

    seen = []

    def spy(name):
        real = getattr(XlaTransfer, name)

        def spying(self, state, slots, grads, *args, **kwargs):
            fields = len(args[-1].touched_fields(grads))
            whole, chips = slots, 1
            if self.route_mode(state) == "inside":
                # one call a chip: each reports an equal part of the whole
                whole, chips = jax.lax.all_gather(slots, self.axis), \
                    self.shards
            jax.debug.callback(lambda s: seen.append(
                fields * np.unique(s[s >= 0]).size / chips), whole)
            return real(self, state, slots, grads, *args, **kwargs)
        monkeypatch.setattr(XlaTransfer, name, spying)

    spy("push")           # (state, slots, grads, access)
    spy("push_span")      # (state, slots, grads, counts, access): CBOW's v
    model, fed = _pairs_run(sg, {}, 1, tmp_path)
    jax.effects_barrier()
    steps = len(fed.valid)
    # two pushes a step, sparse or (at this toy capacity, skip-gram's
    # target push) dense; a split step makes each on all 8 chips
    chips = 1 if sg else 8
    assert steps > 4 and len(seen) == 2 * steps * chips
    assert model.train_metrics["rows_written_per_step"] == pytest.approx(
        sum(seen) / steps, rel=1e-6)
    assert min(seen) > 0


def _tile_copies(rows, block):
    """Copies the tile kernel makes one way a field for the ascending
    distinct ``rows``, a grid step ``block`` slots."""
    from swiftmpi_tpu.transfer.tile_rmw import RUN
    from tests.test_write_back import copies_walked

    return np.count_nonzero(copies_walked(rows, len(rows), block, RUN))


@pytest.mark.parametrize("case", ["apart", "adjacent", "every_row", "drawn",
                                  "all_padding", "cpu"])
def test_tile_copies_count_the_runs_the_kernel_cuts(case, monkeypatch):
    """A push's third count (`XlaTransfer.count_rows_written`): the copies
    the tile kernel started one way, times the fields touched — against a
    numpy count of the runs of adjacent tiles, cut at `tile_rmw.RUN` and
    at the grid step's edge, on crafted pushes: no two named tiles
    adjacent (as many copies as tiles), all adjacent, every row of
    adjacent tiles, a random draw with duplicates and padding, a push of
    padding alone; never more than the tiles, and 0 with them where
    another form writes."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from swiftmpi_tpu.parameter import w2v_access
    from swiftmpi_tpu.transfer import tile_rmw, xla

    monkeypatch.setattr(xla, "_TILE_SLOT_AS_SWEPT_BYTES", 0)
    monkeypatch.setattr(tile_rmw, "BLOCK", 16)
    rng = np.random.default_rng(len(case))
    capacity, width, B = 803, 128, 96
    slots = {
        "apart": 16 * rng.permutation(50)[:40] + rng.integers(0, 8, 40),
        "adjacent": 8 * (10 + rng.permutation(40))
        + rng.integers(0, 8, 40),
        "every_row": rng.permutation(np.arange(80, 160)),
        "all_padding": np.zeros(0, np.int64),
    }.get(case, rng.integers(0, capacity, 70))
    slots = np.concatenate([slots, rng.choice(slots, 8)])[:B] \
        if len(slots) else slots
    slots = rng.permutation(np.concatenate(
        [slots, np.full(B - len(slots), -1)])).astype(np.int32)
    access = w2v_access(0.3, width)
    backend = xla.XlaTransfer(dense_apply=False,
                              platform="cpu" if case == "cpu" else "tpu")
    state = {f: jnp.asarray(rng.random((capacity, width)) + 0.5,
                            jnp.float32) for f in access.fields}

    def push(state, slots, g):
        with backend.count_rows_written() as tape:
            new = backend.push(state, slots, {"h": g}, access, mean=True)
        return new, sum(tape)
    with pltpu.force_tpu_interpret_mode():
        _, counts = jax.jit(push)(state, slots, jnp.asarray(
            rng.normal(size=(B, width)), jnp.float32))
    rows = np.unique(slots[slots >= 0])
    tiles = len(np.unique(rows >> 3))
    # the partial tile at the fields' end is not the kernel's
    copies = _tile_copies(rows[rows < capacity - capacity % 8], 16)
    want = [2 * len(rows), 2 * tiles, 2 * copies]
    if case == "cpu":
        assert set(backend.resolved_write_back.values()) <= {"per_row",
                                                             "sweep"}
        want[1:] = 0, 0
    assert counts.tolist() == want
    assert copies <= tiles
    if case == "apart":
        assert copies == tiles == 40
    elif case == "adjacent":        # 40 tiles over three grid steps
        assert 40 // tile_rmw.RUN <= copies <= 40 // tile_rmw.RUN + 3
    elif case == "every_row":
        assert (tiles, copies) == (10, 5)   # two tiles fill a grid step


@pytest.mark.parametrize("sg", [0, 1], ids=["cbow", "sg"])
def test_tiles_written_counts_the_tiles_the_kernel_moves(sg, monkeypatch,
                                                         tmp_path):
    """``tiles_written_per_step``: where a push takes the tile kernel
    (one TPU, f32 rows of whole 128-lane tiles: here one CPU device whose
    transfer is told it is one, the kernel in Pallas' interpret mode), the
    distinct 8-row tiles its distinct valid rows lie in, times the fields
    it touches; against the slots each push was handed.  It reads 0 where
    every push is written another way.  ``tile_copies_per_step`` beside
    it: the copies that move those tiles one way, `_tile_copies`."""
    import jax
    from jax.experimental.pallas import tpu as pltpu

    from swiftmpi_tpu.cluster.cluster import Cluster
    from swiftmpi_tpu.data.text import (CBOWBatcher, build_vocab,
                                        synthetic_corpus)
    from swiftmpi_tpu.models.word2vec import Word2Vec
    from swiftmpi_tpu.transfer.xla import XlaTransfer
    from swiftmpi_tpu.utils import ConfigParser

    seen = []

    def spy(name):
        real = getattr(XlaTransfer, name)

        def spying(self, state, slots, grads, *args, **kwargs):
            fields = len(args[-1].touched_fields(grads))
            jax.debug.callback(lambda s: seen.append(
                (fields * np.unique(s[s >= 0]).size,
                 fields * np.unique(s[s >= 0] // 8).size,
                 fields * _tile_copies(np.unique(s[s >= 0]), 8))), slots)
            return real(self, state, slots, grads, *args, **kwargs)
        monkeypatch.setattr(XlaTransfer, name, spying)

    spy("push")
    spy("push_span")
    # a table of 64 rows is cheaper swept than the push's slots are moved
    # by tiles: weigh a slot as nothing, as a table of the cells' size does
    from swiftmpi_tpu.transfer import tile_rmw, xla
    monkeypatch.setattr(xla, "_TILE_SLOT_AS_SWEPT_BYTES", 0)
    monkeypatch.setattr(tile_rmw, "BLOCK", 8)     # several grid steps a push
    cfg = ConfigParser().update({
        "cluster": {"transfer": "xla"},
        "word2vec": {"len_vec": 128, "window": 3, "negative": 2, "sg": sg,
                     "sample": -1, "learning_rate": 0.05},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 64, "telemetry": 1,
                   "telemetry_path": str(tmp_path / "telemetry.jsonl")},
    })
    corpus = synthetic_corpus(12, vocab_size=60, length=12, seed=4)
    metrics = {}
    for platform in ("tpu", "cpu"):
        del seen[:]
        cluster = Cluster(cfg, devices=jax.devices()[:1]).initialize()
        cluster.transfer.platform = platform
        # every push sparse: the dense form sweeps the table, no tiles
        cluster.transfer.dense_apply = False
        model = Word2Vec(config=cfg, cluster=cluster)
        model.build_from_vocab(build_vocab(corpus))
        with pltpu.force_tpu_interpret_mode():
            model.train(batcher=CBOWBatcher(corpus, model.vocab,
                                            model.window),
                        niters=1, batch_size=16)
        jax.effects_barrier()
        metrics[platform] = (model.train_metrics, list(seen), set(
            cluster.transfer.resolved_write_back.values()))
    m, pushes, forms = metrics["tpu"]
    steps = len(pushes) // 2
    assert steps > 4 and forms == {"tiles"}
    rows, tiles, copies = (sum(p[k] for p in pushes) / steps
                           for k in (0, 1, 2))
    assert m["rows_written_per_step"] == pytest.approx(rows, rel=1e-6)
    assert m["tiles_written_per_step"] == pytest.approx(tiles, rel=1e-6)
    assert rows / 8 <= tiles < rows          # some rows share a tile
    # ... and some tiles a copy: 60 words fill 8 tiles
    assert m["tile_copies_per_step"] == pytest.approx(copies, rel=1e-6)
    assert tiles / tile_rmw.RUN <= copies < tiles
    m, _, forms = metrics["cpu"]
    assert forms <= {"per_row", "sweep"}
    assert m["rows_written_per_step"] == pytest.approx(rows, rel=1e-6)
    assert m["tiles_written_per_step"] == m["tile_copies_per_step"] == 0


@pytest.mark.parametrize("sg", [0, 1], ids=["cbow", "sg"])
def test_pair_counters_absent_with_telemetry_off(sg, monkeypatch, tmp_path,
                                                 devices8):
    from swiftmpi_tpu.models import word2vec

    def no_sum(self, *batch):
        raise AssertionError("a pair sum was taken with telemetry off")

    monkeypatch.setattr(word2vec._PairCount, "observe", no_sum)
    monkeypatch.setattr(word2vec._PairCount, "observe_span", no_sum)
    model, fed = _pairs_run(sg, {}, 0, tmp_path)
    assert not list(tmp_path.iterdir())
    assert fed.valid and not obs.get_registry().enabled
    assert "pairs_per_step" not in model.train_metrics
    assert "pair_fill_share" not in model.train_metrics
    # ... and the step was built without the row-write counters
    assert "rows_written_per_step" not in model.train_metrics
    assert "tiles_written_per_step" not in model.train_metrics
    assert "tile_copies_per_step" not in model.train_metrics


def test_uncounted_batches_export_no_pair_series(tmp_path, devices8):
    """Batches that are not host arrays (multi-process: already-placed
    global arrays; here, spans handed over as device arrays) are not
    counted: neither the ``train/pairs`` series nor the
    ``train_metrics`` keys exist, so a series at 0 cannot be read as
    "no pairs"."""
    import jax.numpy as jnp

    from swiftmpi_tpu.models import word2vec

    class OnDevice(_RecordingBatcher):
        def epoch_stencil(self, batch_size):
            for batch in self.inner.epoch_stencil(batch_size):
                batch.packed = jnp.asarray(batch.pack())
                yield batch

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sys.modules[__name__], "_RecordingBatcher", OnDevice)
        model, _fed = _pairs_run(0, {}, 1, tmp_path)
    assert model.resolved_rendering.startswith("stencil")
    assert obs.get_registry().enabled
    assert not [k for k in obs.get_registry().series_keys()
                if k.startswith("train/pairs")]
    for key in ("pairs_per_step", "pair_fill_share", "span_rows_per_step"):
        assert key not in model.train_metrics
    assert word2vec._PairCount(obs.get_registry()).steps == 0


def test_pair_series_is_declared():
    from swiftmpi_tpu.obs import catalog
    assert "train/pairs" in catalog.SERIES
    assert catalog.declared("train/pairs")


# -- the sparse layers' scopes and counters (models/trainer.py) ---------------

def _sparse_trainer(seq, topk, layers=2, seqs=1):
    import jax

    from swiftmpi_tpu.models.trainer import Trainer
    from swiftmpi_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=32, d_model=16, n_layers=layers, n_heads=2, d_head=4,
        d_ff=16, max_seq=seq, attention="blockwise", attn_block=8,
        remat=True, remat_policy="full", qk_norm=True,
        layer_ops=("sparse",) * layers, layer_ffns=("dense",) * layers,
        index_heads=2, index_head_dim=4, index_topk=topk, init_std=0.3)
    trainer = Trainer(cfg, aux_weight=0.0, warmup_steps=1, decay_steps=10)
    rng = np.random.default_rng(49)
    batches = [rng.integers(0, 32, (seqs, seq)).astype(np.int32)
               for _ in range(2)]
    return trainer, trainer.init_state(jax.random.key(0)), batches


@pytest.mark.parametrize("seq,topk,layers,seqs", [
    (32, 8, 2, 1), (24, 5, 1, 2), (16, 64, 3, 1)])
def test_selection_counters_equal_the_closed_forms(seq, topk, layers, seqs):
    """``selected_keys_per_query`` and ``selected_pair_share`` are counted on
    the device from the selection the attention used: ``sum_t min(t + 1, k)``
    keys over a sequence's queries, whatever the layers and the batch (a top
    k past the sequence keeps the causal triangle: 100 %), and the objective
    the step returned is its two parts."""
    from swiftmpi_tpu import obs

    obs.reset_for_tests()
    obs.set_enabled(True)
    try:
        trainer, state, batches = _sparse_trainer(seq, topk, layers, seqs)
        _state, losses = trainer.run(state, iter(batches))
        m = trainer.train_metrics
    finally:
        obs.reset_for_tests()
    kept = sum(min(t + 1, topk) for t in range(seq))
    assert m["selected_keys_per_query"] == pytest.approx(kept / seq, rel=1e-6)
    assert m["selected_pair_share"] == pytest.approx(
        100.0 * kept / (seq * (seq + 1) / 2), rel=1e-6)
    assert m["index_loss_per_layer"] == pytest.approx(
        m["index_loss"] / layers, rel=1e-6)
    assert m["index_loss"] > 0.0
    assert m["main_loss"] + m["index_loss"] == pytest.approx(
        float(np.mean([float(x) for x in losses])), rel=1e-5)


def test_selection_counters_absent_with_telemetry_off():
    from swiftmpi_tpu import obs

    obs.reset_for_tests()
    trainer, state, batches = _sparse_trainer(16, 4)
    trainer.run(state, iter(batches))
    assert "selected_keys_per_query" not in trainer.train_metrics
    assert "index_loss" not in trainer.train_metrics


def test_sparse_scopes_are_in_the_compiled_step():
    """``sparse_attention``, ``indexer`` and ``index_select`` are declared
    (``DEVICE_SCOPES``, ``LAYER_SCOPES``) and every one names instructions of
    the compiled step, forward and transposed: the innermost known scope of
    an ``op_name`` is its phase."""
    from swiftmpi_tpu import obs
    from swiftmpi_tpu.obs import catalog, costs

    scopes = ("sparse_attention", "indexer", "index_select")
    assert all(catalog.DEVICE_SCOPES[s] == s for s in scopes)
    assert set(scopes) <= set(catalog.LAYER_SCOPES)
    assert costs.phase_of(
        "jit(train_step)/sparse_attention/indexer/index_select/while") \
        == "index_select"
    assert costs.phase_of(
        "jit(train_step)/transpose(jvp(sparse_attention))/indexer/dot") \
        == "indexer"
    obs.reset_for_tests()
    obs.set_enabled(True)
    try:
        trainer, state, batches = _sparse_trainer(16, 4)
        trainer.run(state, iter(batches))
        phases = set(costs.phase_map("trainer_step")["phase"].values())
    finally:
        obs.reset_for_tests()
    assert set(scopes) <= phases
