"""smtpu-lint engine tests (ISSUE 11): per-rule golden fixtures (each
origin bug reproduced as a tiny snippet that must trip, plus the
corrected twin that must pass), suppression and baseline semantics,
JSON schema, and the repo-wide lint-clean assertion that IS the gate.
"""

import json
import textwrap

import pytest

from swiftmpi_tpu.analysis import core
from swiftmpi_tpu.analysis.lint import main as lint_main


def lint_src(tmp_path, rel, src, ops=None):
    """Write ``src`` at ``tmp_path/rel`` (path scoping matters — rules
    key off serve/, io/pipeline.py, transfer/) and lint just that file;
    returns the NEW findings."""
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    if ops is not None:
        d = tmp_path / "docs"
        d.mkdir(exist_ok=True)
        (d / "OPERATIONS.md").write_text(ops)
    new, _ = core.run_lint(paths=[str(p)], root=str(tmp_path))
    return new


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# DONATE-ESCAPE (the PR-8 bug class)

_DONATE_HEADER = """\
    from functools import partial
    import jax

    @partial(jax.jit, donate_argnums=0)
    def step(state, x):
        return state
"""


def test_donate_escape_trips_on_read_after_donation(tmp_path):
    new = lint_src(tmp_path, "pkg/train.py", _DONATE_HEADER + """
    def train(state, xs):
        out = step(state, xs)
        stash = state
        return out, stash
    """)
    assert [f.rule for f in new] == ["DONATE-ESCAPE"]
    assert "donated" in new[0].message


def test_donate_escape_passes_on_rebind(tmp_path):
    new = lint_src(tmp_path, "pkg/train.py", _DONATE_HEADER + """
    def train(state, xs):
        for x in xs:
            state = step(state, x)
        return state
    """)
    assert "DONATE-ESCAPE" not in rules_of(new)


def test_donate_escape_trips_on_closure_capture(tmp_path):
    new = lint_src(tmp_path, "pkg/train.py", _DONATE_HEADER + """
    def train(state, xs):
        out = step(state, xs)
        def snapshot():
            return state
        return out, snapshot
    """)
    assert "DONATE-ESCAPE" in rules_of(new)
    assert any("closure" in f.message for f in new)


def test_donate_escape_traces_factory_method_chain(tmp_path):
    # the literal PR-8 shape: a donating step built by a factory and
    # bound to self, with the pre-step state stashed after dispatch
    new = lint_src(tmp_path, "pkg/model.py", """
    from functools import partial
    import jax

    class Model:
        def __init__(self):
            self._step = self._build_step()

        def _build_step(self):
            @partial(jax.jit, donate_argnums=0)
            def f(state):
                return state
            return f

        def train(self, state):
            new_state = self._step(state)
            self.snapshot = state
            return new_state
    """)
    assert "DONATE-ESCAPE" in rules_of(new)


def test_donate_escape_passes_when_copied_before(tmp_path):
    new = lint_src(tmp_path, "pkg/model.py", _DONATE_HEADER + """
    import jax

    def train(state, xs):
        host_copy = jax.device_get(state)
        state = step(state, xs)
        return state, host_copy
    """)
    assert "DONATE-ESCAPE" not in rules_of(new)


# ---------------------------------------------------------------------------
# READER-PURE-HOST (the XLA:CPU rendezvous-deadlock class)

def test_reader_pure_host_trips_on_device_ops(tmp_path):
    new = lint_src(tmp_path, "pkg/serve/reader.py", """
    import jax.numpy as jnp

    def read_rows(table, idx):
        return jnp.take(table, idx, axis=0)
    """)
    assert rules_of(new) == {"READER-PURE-HOST"}
    assert len(new) >= 2          # the import and the use


def test_reader_pure_host_passes_on_numpy(tmp_path):
    new = lint_src(tmp_path, "pkg/serve/reader.py", """
    import numpy as np

    def read_rows(table, idx):
        return np.take(table, idx, axis=0)
    """)
    assert new == []


def test_snapshot_allows_device_get_but_not_jit(tmp_path):
    new = lint_src(tmp_path, "pkg/serve/snapshot.py", """
    import jax

    def copy_out(x):
        return jax.device_get(x)

    def bad(fn):
        return jax.jit(fn)
    """)
    assert [f.rule for f in new] == ["READER-PURE-HOST"]
    assert "jax.jit" in new[0].message


# ---------------------------------------------------------------------------
# PRODUCER-NO-RNG / PRODUCER-NO-DEVICE (the PR-5 bit-identity contract)

def test_producer_no_rng_trips(tmp_path):
    new = lint_src(tmp_path, "pkg/io/pipeline.py", """
    import jax

    def produce(key, batch):
        key, sub = jax.random.split(key)
        return sub, batch
    """)
    assert "PRODUCER-NO-RNG" in rules_of(new)


def test_producer_no_rng_passes_outside_pipeline(tmp_path):
    new = lint_src(tmp_path, "pkg/models/w2v.py", """
    import jax

    def draw(key):
        return jax.random.split(key)
    """)
    assert "PRODUCER-NO-RNG" not in rules_of(new)


def test_producer_no_device_trips_on_default_device(tmp_path):
    new = lint_src(tmp_path, "pkg/io/pipeline.py", """
    import jax

    def place(x):
        with jax.default_device(jax.devices()[0]):
            return jax.device_put(x)
    """)
    msgs = [f for f in new if f.rule == "PRODUCER-NO-DEVICE"]
    assert len(msgs) >= 2         # default_device consult + 1-arg put


def test_producer_no_device_passes_with_explicit_sharding(tmp_path):
    new = lint_src(tmp_path, "pkg/io/pipeline.py", """
    import jax

    def place(x, sharding):
        return jax.device_put(x, sharding)
    """)
    assert "PRODUCER-NO-DEVICE" not in rules_of(new)


# ---------------------------------------------------------------------------
# LEDGER-MONOTONIC (the PR-6 traffic()-never-resets contract)

def test_ledger_trips_on_counter_reset(tmp_path):
    new = lint_src(tmp_path, "pkg/transfer/fancy.py", """
    class FancyTransfer:
        def finish_epoch(self):
            st = self._wire_state()
            st["wire_bytes"] = 0

        def reset_traffic(self):
            pass
    """)
    assert [f.rule for f in new] == ["LEDGER-MONOTONIC"] * 2


def test_ledger_passes_on_increment(tmp_path):
    new = lint_src(tmp_path, "pkg/transfer/fancy.py", """
    class FancyTransfer:
        def push(self, n):
            st = self._wire_state()
            st["wire_bytes"] += n
    """)
    assert new == []


def test_ledger_trips_on_hand_rolled_delta(tmp_path):
    new = lint_src(tmp_path, "pkg/bench_thing.py", """
    def measure(tr, run):
        before = tr.traffic()
        run()
        after = tr.traffic()
        return after["wire_bytes"] - before["wire_bytes"]
    """)
    assert "LEDGER-MONOTONIC" in rules_of(new)
    assert "traffic_delta" in new[0].message


def test_ledger_passes_on_traffic_delta(tmp_path):
    new = lint_src(tmp_path, "pkg/bench_thing.py", """
    def measure(tr, run):
        before = tr.traffic()
        run()
        return tr.traffic_delta(before)
    """)
    assert new == []


# ---------------------------------------------------------------------------
# TELEMETRY-CATALOG

def test_telemetry_trips_on_undeclared_series(tmp_path):
    new = lint_src(tmp_path, "pkg/thing.py", """
    def record(reg):
        reg.counter("transfer/wire_bytez").inc(1)
    """)
    assert rules_of(new) == {"TELEMETRY-CATALOG"}


def test_telemetry_passes_on_declared_series_and_prefix(tmp_path):
    new = lint_src(tmp_path, "pkg/thing.py", """
    def record(reg, knob):
        reg.histogram("phase_ms").observe(1.0)
        reg.gauge(f"control/{knob}").set(2)
    """)
    assert new == []


def test_telemetry_trips_on_undeclared_fstring_stem(tmp_path):
    new = lint_src(tmp_path, "pkg/thing.py", """
    def record(reg, k):
        reg.gauge(f"bogus_{k}").set(1)
    """)
    assert rules_of(new) == {"TELEMETRY-CATALOG"}


def test_telemetry_checks_obs_inc_wrapper(tmp_path):
    new = lint_src(tmp_path, "pkg/transfer/fancy.py", """
    class FancyTransfer:
        def push(self):
            self._obs_inc("wire_bytes", 1)
            self._obs_inc("not_a_ledger_key", 1)
    """)
    assert [f.rule for f in new] == ["TELEMETRY-CATALOG"]
    assert "transfer/not_a_ledger_key" in new[0].message


def test_telemetry_covers_collective_series(tmp_path):
    """ISSUE 19 satellite: the collective-decision mirror
    (`transfer/collective{kind=}`) and the sparse-allreduce byte delta
    (`transfer/hot_psum_bytes_saved`) are catalog-declared; a typo'd
    collective key trips like any other ledger key."""
    new = lint_src(tmp_path, "pkg/transfer/fancy.py", """
    class FancyTransfer:
        def reconcile(self):
            self._obs_inc("collective", 1, kind="sparse_ar")
            self._obs_inc("hot_psum_bytes_saved", 4096)
            self._obs_inc("hot_psum_bytes_savd", 4096)
    """)
    assert [f.rule for f in new] == ["TELEMETRY-CATALOG"]
    assert "transfer/hot_psum_bytes_savd" in new[0].message


def test_telemetry_covers_collector_module(tmp_path):
    """ISSUE 12 satellite: the fleet collector's registry mirror is NOT
    exempt from the catalog — its fleet/* gauges must be declared like
    any other series, and a typo'd fleet series trips the rule."""
    new = lint_src(tmp_path, "pkg/obs/collector.py", """
    def mirror(reg, summary):
        reg.gauge("fleet/step_ms_skew").set(summary["skew"])
        reg.gauge("fleet/wire_bytes_imbalance").set(summary["imb"])
        reg.gauge("fleet/members_dead").set(0)
    """)
    assert new == []


def test_telemetry_trips_on_undeclared_fleet_series(tmp_path):
    new = lint_src(tmp_path, "pkg/obs/collector.py", """
    def mirror(reg):
        reg.gauge("fleet/step_ms_skoo").set(1.0)
    """)
    assert rules_of(new) == {"TELEMETRY-CATALOG"}
    assert "fleet/step_ms_skoo" in new[0].message


def test_telemetry_covers_numerics_series(tmp_path):
    """ISSUE 13 satellite: the numerics health plane's series are
    catalog-declared like any other — the collector sampler, the
    detector's severity-labeled anomaly counter, and the ef_mass
    field-labeled gauge all pass as written."""
    new = lint_src(tmp_path, "pkg/obs/numerics.py", """
    def sample(reg, ef_mass, sev):
        reg.gauge("numerics/grad_norm").set(1.0)
        reg.gauge("numerics/ef_mass", field="w").set(0.1)
        reg.counter("numerics/nonfinite").set_total(0.0)
        reg.counter("numerics/quant_err").set_total(0.0)
        reg.counter("numerics/anomalies", severity=sev).inc()
        reg.gauge("fleet/grad_norm_divergence").set(1.0)
        reg.gauge("fleet/anomalies").set(0.0)
    """)
    assert new == []


def test_telemetry_trips_on_undeclared_numerics_series(tmp_path):
    new = lint_src(tmp_path, "pkg/obs/numerics.py", """
    def sample(reg):
        reg.gauge("numerics/grad_nrom").set(1.0)
    """)
    assert rules_of(new) == {"TELEMETRY-CATALOG"}
    assert "numerics/grad_nrom" in new[0].message


def test_telemetry_covers_compile_series(tmp_path):
    """ISSUE 14 satellite: the compiler-cost catalog and the triggered
    profiler write catalog-declared series like any other plane — the
    fn-labeled compile counters/gauges and the phase-labeled profile
    attribution gauges all pass as written."""
    new = lint_src(tmp_path, "pkg/obs/costs.py", """
    def book(reg, name, dt_ms, ph):
        reg.counter("compile/compiles", fn=name).inc()
        reg.counter("compile/compile_ms", fn=name).inc(dt_ms)
        reg.counter("compile/retraces", fn=name).inc()
        reg.gauge("compile/flops", fn=name).set(1.0)
        reg.gauge("compile/bytes", fn=name).set(1.0)
        reg.gauge("compile/peak_bytes", fn=name).set(1.0)
        reg.counter("profile/sessions").inc()
        reg.counter("profile/steps").inc(5)
        reg.gauge("profile/device_ms", phase=ph).set(1.0)
        reg.gauge("profile/host_ms", phase=ph).set(1.0)
        reg.gauge("profile/skew_ms", phase=ph).set(0.0)
    """)
    assert new == []


def test_telemetry_trips_on_undeclared_compile_series(tmp_path):
    new = lint_src(tmp_path, "pkg/obs/costs.py", """
    def book(reg, name):
        reg.counter("compile/retracez", fn=name).inc()
    """)
    assert rules_of(new) == {"TELEMETRY-CATALOG"}
    assert "compile/retracez" in new[0].message


def test_telemetry_covers_ship_series(tmp_path):
    """ISSUE 17 satellite: the snapshot shipper/replica book catalog-
    declared series like any other plane — the delta/full publish
    counters, the fmt-labeled decision counter, the version-chain
    gauges, the replica-labeled replay gauges, and the fleet serve
    mirrors all pass as written."""
    new = lint_src(tmp_path, "pkg/serve/shipper.py", """
    def book(reg, dec, ident):
        reg.counter("serve/delta_publishes").inc(1)
        reg.counter("serve/delta_bytes").inc(100)
        reg.counter("serve/delta_fmt", fmt=dec).inc(1)
        reg.counter("serve/full_publishes").inc(1)
        reg.counter("serve/full_bytes").inc(100)
        reg.gauge("serve/ship_version").set(3)
        reg.gauge("serve/replica_version", replica=ident).set(3)
        reg.gauge("serve/replica_lag", replica=ident).set(0)
        reg.gauge("serve/staleness_s", replica=ident).set(0.1)
        reg.gauge("fleet/serve_replicas").set(3)
        reg.gauge("fleet/serve_qps").set(400.0)
        reg.gauge("fleet/serve_lag_max").set(0)
        reg.gauge("fleet/serve_version").set(3)
    """)
    assert new == []


def test_telemetry_trips_on_undeclared_ship_series(tmp_path):
    new = lint_src(tmp_path, "pkg/serve/shipper.py", """
    def book(reg):
        reg.counter("serve/delta_bytez").inc(100)
    """)
    assert rules_of(new) == {"TELEMETRY-CATALOG"}
    assert "serve/delta_bytez" in new[0].message


def test_telemetry_covers_plan_compiler_series(tmp_path):
    """ISSUE 18 satellite: the TrafficPlan compiler's ledger mirrors —
    compile/cache-hit counters and the fmt-labeled 5-way decision series
    (fmt=sketch included) — are catalog-declared and pass as written."""
    new = lint_src(tmp_path, "pkg/obs/planview.py", """
    def book(reg):
        reg.counter("transfer/plan_compiles", backend="xla").inc(1)
        reg.counter("transfer/plan_cache_hits", backend="xla").inc(1)
        reg.counter("transfer/window_fmt", backend="xla",
                    fmt="sketch").inc(1)
    """)
    assert new == []


def test_telemetry_trips_on_undeclared_plan_series(tmp_path):
    new = lint_src(tmp_path, "pkg/obs/planview.py", """
    def book(reg):
        reg.counter("transfer/plan_compilez", backend="xla").inc(1)
    """)
    assert rules_of(new) == {"TELEMETRY-CATALOG"}
    assert "transfer/plan_compilez" in new[0].message


def test_telemetry_checks_both_ifexp_branches(tmp_path):
    new = lint_src(tmp_path, "pkg/thing.py", """
    def record(reg, ok):
        reg.counter(
            "health/probe_ok" if ok else "health/probe_typo").inc(1)
    """)
    assert rules_of(new) == {"TELEMETRY-CATALOG"}


# ---------------------------------------------------------------------------
# LOCK-GUARD

_LOCK_CLASS = """\
    import threading

    class Publisher:
        def __init__(self):
            self._lock = threading.Lock()
            self._latest = None      # guarded-by: _lock
            self._history = []       # guarded-by: _lock
            self._free = 0           # no annotation
"""


def test_lock_guard_trips_outside_lock(tmp_path):
    new = lint_src(tmp_path, "pkg/pub.py", _LOCK_CLASS + """
        def publish(self, snap):
            self._history.append(snap)
            self._latest = snap
    """)
    assert [f.rule for f in new] == ["LOCK-GUARD"] * 2


def test_lock_guard_passes_inside_lock(tmp_path):
    new = lint_src(tmp_path, "pkg/pub.py", _LOCK_CLASS + """
        def publish(self, snap):
            with self._lock:
                self._history.append(snap)
                self._latest = snap
            self._free += 1
    """)
    assert new == []


def test_lock_guard_ignores_wrong_lock(tmp_path):
    new = lint_src(tmp_path, "pkg/pub.py", _LOCK_CLASS + """
        def publish(self, snap, other_lock):
            with other_lock:
                self._latest = snap
    """)
    assert "LOCK-GUARD" in rules_of(new)


# ---------------------------------------------------------------------------
# EPOCH-GUARD (the ISSUE 16 elastic-membership invariant)

def test_epoch_guard_trips_on_unannotated_adopt(tmp_path):
    new = lint_src(tmp_path, "pkg/worker.py", """
    class Worker:
        def sync(self, table):
            self.member_table = table
            self.epoch = table.epoch
    """)
    assert [f.rule for f in new] == ["EPOCH-GUARD"]
    assert "epoch-guard" in new[0].message
    assert "sync" in new[0].message


def test_epoch_guard_trips_on_unannotated_write_call(tmp_path):
    new = lint_src(tmp_path, "pkg/sup.py", """
    from swiftmpi_tpu.cluster import membership as mem

    def publish(fleet_dir, table):
        mem.write_membership(fleet_dir, table)
    """)
    assert [f.rule for f in new] == ["EPOCH-GUARD"]


def test_epoch_guard_passes_with_annotation(tmp_path):
    new = lint_src(tmp_path, "pkg/worker.py", """
    class Worker:
        def sync(self, table):
            if table.epoch < self.epoch:
                raise ValueError("stale epoch")
            # epoch-guard: regression raised above
            self.member_table = table
            self.epoch = table.epoch
    """)
    assert new == []


def test_epoch_guard_ignores_class_defaults_and_init(tmp_path):
    # class-level defaults and __init__ run happens-before publication
    # (no epoch exists yet) — neither needs the annotation
    new = lint_src(tmp_path, "pkg/backend.py", """
    class Backend:
        _membership_epoch = -1
        _live_ranks = None

        def __init__(self):
            self.member_table = None
    """)
    assert new == []


def test_epoch_guard_skips_the_choke_point_itself(tmp_path):
    new = lint_src(tmp_path, "pkg/mem.py", """
    def write_membership(dirpath, table):
        owner_of_shard = tuple(table.owner_of_shard)
        return owner_of_shard
    """)
    assert new == []


# ---------------------------------------------------------------------------
# KNOB-DOC

def test_knob_doc_trips_without_entry(tmp_path):
    new = lint_src(tmp_path, "pkg/mod.py", """
    def setup(config):
        return config.get_or("fancy", "speed", 3).to_int32()
    """, ops="# Operations\n\nnothing here\n")
    assert rules_of(new) == {"KNOB-DOC"}
    assert "[fancy] speed" in new[0].message


def test_knob_doc_passes_with_entry_and_tracks_alias(tmp_path):
    new = lint_src(tmp_path, "pkg/mod.py", """
    def setup(config):
        g = config.get_or
        a = g("fancy", "speed", 3).to_int32()
        b = config.get("fancy", "mode")
        return a, b
    """, ops="| `[fancy] speed` | 3 | x |\n`[fancy] mode` docs\n")
    assert new == []


def test_knob_doc_ignores_plain_dict_get(tmp_path):
    new = lint_src(tmp_path, "pkg/mod.py", """
    def lookup(meta):
        return meta.get("query_field", "vectors")
    """, ops="")
    assert "KNOB-DOC" not in rules_of(new)


# ---------------------------------------------------------------------------
# PLAN-DISPATCH (the PR-18 single-dispatch-point invariant)

def test_plan_dispatch_trips_on_format_branch_in_backend(tmp_path):
    new = lint_src(tmp_path, "pkg/transfer/custom.py", """
    def exchange(self, state, fmt):
        if fmt == "bitmap":
            return state
        if fmt in ("sparse_q", "sparse_sketch"):
            return state
        return state
    """)
    assert [f.rule for f in new] == ["PLAN-DISPATCH", "PLAN-DISPATCH"]
    assert "TrafficPlan interpreter" in new[0].message


def test_plan_dispatch_trips_on_pricing_call_in_backend(tmp_path):
    new = lint_src(tmp_path, "pkg/transfer/rdma.py", """
    def exchange(self, rows, cap, rb):
        return self.decide_wire_format(rows, cap, rb)
    """)
    assert [f.rule for f in new] == ["PLAN-DISPATCH"]
    assert "decide_wire_format" in new[0].message


def test_plan_dispatch_trips_on_collective_branch_in_backend(tmp_path):
    """Collective selection is the same dispatch in another plan-table
    column: a backend comparing against `sparse_allreduce` (or picking
    between the dense collectives by name) trips like a wire-format
    branch."""
    new = lint_src(tmp_path, "pkg/transfer/custom.py", """
    def reconcile(self, state, coll):
        if coll == "sparse_allreduce":
            return state
        if coll in ("psum_scatter",):
            return state
        return state
    """)
    assert [f.rule for f in new] == ["PLAN-DISPATCH", "PLAN-DISPATCH"]
    assert "collective 'sparse_allreduce'" in new[0].message


def test_plan_dispatch_trips_on_hot_pricing_call_in_backend(tmp_path):
    new = lint_src(tmp_path, "pkg/transfer/rdma.py", """
    def reconcile(self, n_hot, wb):
        return self.compile_hot_plan(n_hot, wb)
    """)
    assert [f.rule for f in new] == ["PLAN-DISPATCH"]
    assert "compile_hot_plan" in new[0].message


def test_plan_dispatch_collective_passes_in_interpreter_and_codec(
        tmp_path):
    """api.py/plan.py own the collective dispatch, and the
    sparse_allreduce codec module implements it — none of them trip."""
    src = """
    def interp(self, transfer, plan):
        if plan.collective == "sparse_allreduce":
            return self.price_hot_collectives(8, 36, 0.1)
    """
    for rel in ("pkg/transfer/api.py", "pkg/transfer/plan.py",
                "pkg/transfer/sparse_allreduce.py",
                "pkg/control/tuner.py"):
        assert "PLAN-DISPATCH" not in rules_of(
            lint_src(tmp_path, rel, src)), rel


def test_plan_dispatch_exempts_interpreter_codec_and_non_transfer(tmp_path):
    """The interpreter/plan/codec modules ARE where the wire-format
    question lives (delta.py is the PR-17 codec precedent), and the
    rule is scoped to transfer/ — a controller comparing format names
    is out of its jurisdiction."""
    src = """
    def interp(self, transfer, plan):
        if plan.wire_format == "sparse_sketch":
            return transfer.decide_wire_format(1, 2, 3)
    """
    for rel in ("pkg/transfer/api.py", "pkg/transfer/plan.py",
                "pkg/transfer/sketch.py", "pkg/transfer/delta.py",
                "pkg/control/tuner.py"):
        assert "PLAN-DISPATCH" not in rules_of(
            lint_src(tmp_path, rel, src)), rel


# ---------------------------------------------------------------------------
# suppression + baseline semantics

def test_line_suppression(tmp_path):
    new = lint_src(tmp_path, "pkg/serve/reader.py", """
    import jax.numpy as jnp  # smtpu-lint: disable=READER-PURE-HOST

    def f(x):
        return jnp.sum(x)    # smtpu-lint: disable=READER-PURE-HOST
    """)
    assert new == []


def test_block_suppression_covers_def_body(tmp_path):
    new = lint_src(tmp_path, "pkg/serve/reader.py", """
    def f(x):  # smtpu-lint: disable=READER-PURE-HOST
        import jax.numpy as jnp
        return jnp.sum(x)

    def g(x):
        import jax.numpy as jnp
        return jnp.sum(x)
    """)
    assert rules_of(new) == {"READER-PURE-HOST"}
    assert all(f.line >= 6 for f in new)       # only g() trips


def test_file_suppression(tmp_path):
    new = lint_src(tmp_path, "pkg/serve/reader.py", """
    # smtpu-lint: disable-file=READER-PURE-HOST
    import jax.numpy as jnp

    def f(x):
        return jnp.sum(x)
    """)
    assert new == []


def test_suppression_is_per_rule(tmp_path):
    new = lint_src(tmp_path, "pkg/io/pipeline.py", """
    import jax

    def produce(key, x):
        k = jax.random.split(key)  # smtpu-lint: disable=PRODUCER-NO-DEVICE
        return k, x
    """)
    # suppressing the WRONG rule leaves the real finding standing
    assert "PRODUCER-NO-RNG" in rules_of(new)


def test_baseline_roundtrip_and_line_drift(tmp_path):
    src = """
    import jax.numpy as jnp
    """
    p = tmp_path / "pkg" / "serve" / "reader.py"
    p.parent.mkdir(parents=True)
    p.write_text(textwrap.dedent(src))
    new, old = core.run_lint(paths=[str(p)], root=str(tmp_path))
    assert len(new) == 1 and old == []

    bl_path = tmp_path / core.BASELINE_NAME
    core.write_baseline(str(bl_path), new, justification="fixture")
    bl = core.load_baseline(str(bl_path))
    assert set(bl) == {new[0].fingerprint}

    # same finding now lands in `baselined`, even after line drift
    p.write_text("# a new leading comment\n" + textwrap.dedent(src))
    new2, old2 = core.run_lint(paths=[str(p)], root=str(tmp_path),
                               baseline=bl)
    assert new2 == [] and len(old2) == 1
    assert old2[0].fingerprint == new[0].fingerprint


def test_baseline_justify_flags_placeholder_justification(tmp_path):
    """A suppression without a reason is not a suppression: the
    write_baseline placeholder (or any blank/TODO text) keeps the entry
    gating as BASELINE-JUSTIFY until a human-written reason lands."""
    p = tmp_path / "pkg" / "serve" / "reader.py"
    p.parent.mkdir(parents=True)
    p.write_text("import jax.numpy as jnp\n")
    new, _ = core.run_lint(paths=[str(p)], root=str(tmp_path))
    bl_path = tmp_path / core.BASELINE_NAME

    for j in (None, "", "   ", "TODO: justify or fix", "todo later"):
        core.write_baseline(str(bl_path), new,
                            **({} if j is None else {"justification": j}))
        got, old = core.run_lint(paths=[str(p)], root=str(tmp_path),
                                 baseline=core.load_baseline(str(bl_path)))
        assert [f.rule for f in got] == ["BASELINE-JUSTIFY"], j
        assert len(old) == 1       # the original finding stays baselined
        assert "justification" in got[0].message
        assert "READER-PURE-HOST" in got[0].message

    # a real reason silences the escalation
    core.write_baseline(str(bl_path), new,
                        justification="host-only fixture reader")
    got, old = core.run_lint(paths=[str(p)], root=str(tmp_path),
                             baseline=core.load_baseline(str(bl_path)))
    assert got == [] and len(old) == 1


def test_parse_error_is_a_finding(tmp_path):
    new = lint_src(tmp_path, "pkg/broken.py", """
    def f(:
    """)
    assert [f.rule for f in new] == ["PARSE"]


# ---------------------------------------------------------------------------
# CLI: JSON schema + exit codes

def test_cli_json_schema_and_exit_codes(tmp_path, capsys):
    p = tmp_path / "pkg" / "serve" / "reader.py"
    p.parent.mkdir(parents=True)
    p.write_text("import jax.numpy as jnp\n")
    out_json = tmp_path / "report.json"

    rc = lint_main(["--root", str(tmp_path), "--format", "json",
                    "--out", str(out_json), str(p)])
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["schema"] == core.JSON_SCHEMA
    assert payload["counts"] == {"new": 1, "baselined": 0}
    f = payload["new"][0]
    assert set(f) == {"rule", "path", "line", "col", "message",
                      "fingerprint"}
    assert f["rule"] == "READER-PURE-HOST"
    # --out archive matches stdout
    assert json.loads(out_json.read_text()) == payload

    p.write_text("import numpy as np\n")
    rc = lint_main(["--root", str(tmp_path), str(p)])
    assert rc == 0


def test_cli_write_baseline(tmp_path, capsys):
    p = tmp_path / "pkg" / "serve" / "reader.py"
    p.parent.mkdir(parents=True)
    p.write_text("import jax.numpy as jnp\n")
    rc = lint_main(["--root", str(tmp_path), "--write-baseline",
                    str(p)])
    assert rc == 0
    bl = json.loads((tmp_path / core.BASELINE_NAME).read_text())
    assert bl["schema"] == core.JSON_SCHEMA
    assert len(bl["findings"]) == 1
    # the freshly-written baseline still carries the deliberate
    # placeholder justification, so the same run now gates on
    # BASELINE-JUSTIFY — grandfathering is a two-step act on purpose
    rc = lint_main(["--root", str(tmp_path), str(p)])
    assert rc == 1
    # writing the actual reason in completes the suppression
    bl["findings"][0]["justification"] = "fixture: host-only reader"
    (tmp_path / core.BASELINE_NAME).write_text(json.dumps(bl))
    rc = lint_main(["--root", str(tmp_path), str(p)])
    assert rc == 0


# ---------------------------------------------------------------------------
# the gate itself

def test_repo_is_lint_clean():
    """The repo must lint clean against its checked-in baseline — this
    assertion IS the tier-1 gate's contract."""
    root = core.repo_root()
    baseline = core.load_baseline(
        str(__import__("os").path.join(root, core.BASELINE_NAME)))
    new, _ = core.run_lint(root=root, baseline=baseline)
    assert new == [], "\n".join(f.render() for f in new)


def test_every_rule_has_a_fixture():
    """Each registered rule id appears in at least one test above."""
    import swiftmpi_tpu.analysis.rules as rules_mod
    src = open(__file__, encoding="utf-8").read()
    for rule in rules_mod.RULES:
        assert rule.id in src, f"no fixture exercises {rule.id}"
