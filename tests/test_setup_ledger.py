"""The start-up ledger (ISSUE 52): set-up spans, JAX's compile events by
program and stage, ``obs.setup_report()``, the train loops' declared spans
and the benchmark's ``setup_ledger`` reader."""

import contextlib
import logging
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from swiftmpi_tpu import obs
from swiftmpi_tpu.data.text import synthetic_corpus
from swiftmpi_tpu.models import transformer as tfm
from swiftmpi_tpu.models.trainer import Trainer
from swiftmpi_tpu.models.word2vec import Word2Vec
from swiftmpi_tpu.obs import catalog
from swiftmpi_tpu.obs import costs as obs_costs
from swiftmpi_tpu.utils import ConfigParser

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stages(report, span=None, program=None):
    """``{stage: (count, seconds)}`` of the rows under ``span`` (a path)
    and of ``program``."""
    out = {}
    for r in report["stages"]:
        if (span is None or r["span"] == span) and \
                (program is None or r["program"] == program):
            n, s = out.get(r["stage"], (0, 0.0))
            out[r["stage"]] = (n + r["count"], s + r["seconds"])
    return out


def _names(report):
    return [s["name"] for s in report["spans"]]


def test_spans_nest_and_report_parent_and_self_time():
    with obs.setup_span("model_build"):
        time.sleep(0.02)
        with obs.setup_span("table_create"):
            time.sleep(0.03)
        with obs.setup_span("key_index"):
            time.sleep(0.01)
    with obs.setup_span("step_build"):
        pass
    spans = obs.setup_report()["spans"]
    assert [(s["name"], s["path"], s["parent"]) for s in spans] == [
        ("model_build", "model_build", None),
        ("table_create", "model_build/table_create", 0),
        ("key_index", "model_build/key_index", 0),
        ("step_build", "step_build", None)]
    build, table, index, _step = spans
    assert build["start_s"] == 0.0 < table["start_s"] < index["start_s"]
    assert table["seconds"] >= 0.03 and index["seconds"] >= 0.01
    assert build["seconds"] >= 0.06
    assert build["self_seconds"] == pytest.approx(
        build["seconds"] - table["seconds"] - index["seconds"])
    assert 0.02 <= build["self_seconds"] < build["seconds"] - 0.04
    assert table["self_seconds"] == table["seconds"]
    assert not any(s["open"] for s in spans)
    # no step yet: nothing to count start-up to
    assert obs.setup_report()["time_to_first_step_s"] is None


def test_a_jit_books_its_stages_under_the_open_span():
    def ledger_toy(x):
        return jnp.sin(x) * 2.0

    x = jnp.ones((8,), jnp.float32)      # its programs: before the span
    f = jax.jit(ledger_toy)
    with obs.setup_span("step_build"):
        f(x)
    got = _stages(obs.setup_report(), "step_build", "ledger_toy")
    assert {k: n for k, (n, _s) in got.items()} == {
        "trace": 1, "lower": 1, "compile": 1}
    assert all(s > 0.0 for _n, s in got.values())
    # a cached dispatch emits no event
    with obs.setup_span("step_build"):
        f(x + 1.0)
    again = _stages(obs.setup_report(), "step_build", "ledger_toy")
    assert {k: n for k, (n, _s) in again.items()} == {
        "trace": 1, "lower": 1, "compile": 1}


@pytest.fixture
def persistent_cache(tmp_path):
    """A persistent compile cache of the test's own, every program kept."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    held = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path / "cache"), True, 0.0, 0)):
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    yield
    for k, v in held.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_a_persistent_cache_hit_is_a_cache_read(persistent_cache):
    def make():
        def cached_toy(x):
            return jnp.cos(x) + 3.0
        return jax.jit(cached_toy)

    x = jnp.ones((16,), jnp.float32)
    with obs.setup_span("model_build"):
        make()(x)                        # compiled, and written
    with obs.setup_span("step_build"):
        make()(x)                        # a fresh handle: read back
    report = obs.setup_report()
    cold = _stages(report, "model_build", "cached_toy")
    warm = _stages(report, "step_build", "cached_toy")
    assert {k: n for k, (n, _s) in cold.items()} == {
        "trace": 1, "lower": 1, "compile": 1}
    assert {k: n for k, (n, _s) in warm.items()} == {
        "trace": 1, "lower": 1, "cache_read": 1}
    assert "cached_toy" in obs_costs.setup_line(report).split(
        "compiled by the backend: ")[1]


def test_an_inner_jit_traces_on_its_own_account():
    @jax.jit
    def inner_toy(x):
        time.sleep(0.05)                 # tracing time, the inner's
        return x + 1.0

    def outer_toy(x):
        return inner_toy(x) * 2.0

    x = jnp.ones((4,), jnp.float32)
    with obs.setup_span("step_build"):
        jax.jit(outer_toy)(x)
    report = obs.setup_report()
    inner = _stages(report, "step_build", "inner_toy")
    outer = _stages(report, "step_build", "outer_toy")
    assert set(inner) == {"trace"} and inner["trace"][1] >= 0.05
    assert set(outer) == {"trace", "lower", "compile"}
    # the outer's own tracing leaves the inner's out
    assert outer["trace"][1] < inner["trace"][1]


def test_kept_with_telemetry_off_and_nothing_in_the_registry():
    reg = obs.get_registry()
    assert not reg.enabled
    with obs.setup_span("model_build"):
        jax.jit(lambda x: x - 1.0)(np.ones((3,), np.float32))
    assert obs.span("dispatch") is obs._NULL_SPAN     # the loop's: as it was
    snap = reg.snapshot()
    assert not snap["hists"] and not snap["counters"] and not snap["gauges"]
    report = obs.setup_report()
    assert _names(report) == ["model_build"]
    assert _stages(report, "model_build")["lower"][0] == 1
    # ... nor with it on: a set-up span is no phase_ms sample
    obs.set_enabled(True)
    with obs.setup_span("step_build"):
        pass
    assert not obs.get_registry().snapshot()["hists"]


def test_start_up_ends_with_the_first_step():
    """After the first ``first_step`` returns only events under a set-up
    span are kept, each told from start-up's by ``startup``."""
    f = obs_costs.track("toy_step", jax.jit(lambda x: x * 3.0))
    x = jnp.ones((5,), jnp.float32)
    assert f.unrun
    with obs.setup_span("state_init"):
        pass
    f(x)                                 # its first call: first_step
    assert not f.unrun
    report = obs.setup_report()
    assert report["time_to_first_step_s"] > 0.0
    assert _stages(report, "first_step")["lower"][0] == 1
    kept = dict(obs_costs.get_ledger().rows)
    jax.jit(lambda x: x / 7.0)(x)        # a program of the steady state
    assert obs_costs.get_ledger().rows == kept
    with obs.setup_span("step_build"):
        jax.jit(lambda x: x / 9.0)(x)    # a later build's
    late = [r for r in obs.setup_report()["stages"]
            if r["span"] == "step_build"]
    assert late and not any(r["startup"] for r in late)
    assert all(r["startup"] for r in obs.setup_report()["stages"]
               if r["span"] != "step_build")


def _w2v_config(word2vec=(), **worker):
    return ConfigParser().update({
        "cluster": {"transfer": "xla"},
        "word2vec": {"len_vec": 16, "window": 2, "negative": 5,
                     "sample": -1, "learning_rate": 0.05,
                     "min_sentence_length": 2, **dict(word2vec)},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 512, **worker}})


@contextlib.contextmanager
def _start_up_lines(caplog):
    """The ``start-up:`` lines the program's logger says inside the block
    (it does not propagate: the handler goes on it)."""
    said, logger = [], logging.getLogger("swiftmpi_tpu")
    caplog.clear()
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="swiftmpi_tpu"):
            yield said
    finally:
        logger.removeHandler(caplog.handler)
        said += [r.getMessage() for r in caplog.records
                 if r.getMessage().startswith("start-up: ")]


def test_word2vec_train_emits_the_declared_spans(caplog):
    corpus = synthetic_corpus(40, vocab_size=60, length=14, seed=8)
    model = Word2Vec(config=_w2v_config())
    with _start_up_lines(caplog) as said:
        model.train(corpus, niters=2, batch_size=64)
    report = obs.setup_report()
    names = _names(report)
    assert set(names) <= set(catalog.SETUP_SPANS)
    assert names == ["model_build", "table_create", "key_index",
                     "sampler_build", "step_build", "first_step"]
    paths = {s["name"]: s["path"] for s in report["spans"]}
    assert paths["table_create"] == "model_build/table_create"
    assert paths["key_index"] == "model_build/key_index"
    assert paths["sampler_build"] == "model_build/sampler_build"
    # the table's init_all under table_create, the step under first_step
    assert _stages(report, "model_build/table_create",
                   "init_all")["lower"][0] == 1
    step = _stages(report, "first_step")
    assert _stages(report, "first_step", "step")["lower"][0] == 1
    assert step["trace"][0] >= 1          # JAX's own placements with it
    first = next(s for s in report["spans"] if s["name"] == "first_step")
    assert sum(s for _n, s in step.values()) <= first["seconds"]
    assert report["time_to_first_step_s"] == pytest.approx(
        first["start_s"] + first["seconds"])
    # one line in the program's log, from the first train() alone
    assert len(said) == 1 and "model_build/key_index=" in said[0]
    # a second train() builds nothing: no set-up span, no second line
    with _start_up_lines(caplog) as said:
        model.train(corpus, niters=1, batch_size=64)
    assert _names(obs.setup_report()) == names and not said


@pytest.mark.parametrize("worker, word2vec, batch", [
    ({"inner_steps": 4}, {}, 512),
    ({}, {"async_mode": "hogwild", "local_steps": 2}, 16),
    ({}, {"local_steps": 2}, 64),
], ids=["fused", "hogwild", "async_pair"])
def test_every_built_program_has_one_first_step(worker, word2vec, batch):
    """A fused loop builds the single step and one program a group
    length, a hogwild loop one step for every group of its epochs, the
    async loop a pair: ``first_step`` once a program that was called,
    whatever the number of calls that follow."""
    corpus = synthetic_corpus(60, vocab_size=100, length=18, seed=2)
    model = Word2Vec(config=_w2v_config(word2vec, **worker))
    model.train(corpus, niters=2, batch_size=batch)
    names = _names(obs.setup_report())
    step = model._step if isinstance(model._step, tuple) else (model._step,)
    programs = [f for f in (*step, *model._fused_cache.values())
                if isinstance(f, obs_costs.TrackedFn)]
    assert names.count("step_build") == 1 + len(model._fused_cache)
    called = [f for f in programs if not f.unrun]
    assert 1 <= names.count("first_step") == len(called)
    assert obs.setup_report()["dropped"] == 0
    model.train(corpus, niters=1, batch_size=batch)
    assert _names(obs.setup_report()) == names


def test_a_handle_inlined_into_another_program_opens_no_span():
    """A tracked handle first called while JAX traces another program on
    the thread is part of that program: its caller's ``first_step``
    alone, and none later for a call of its own."""
    inner = obs_costs.track("inner_fn", jax.jit(lambda x: x * 2.0))

    def outer_fn(x):
        return inner(x) + 1.0

    outer = obs_costs.track("outer_fn", jax.jit(outer_fn))
    x = jnp.ones((4,), jnp.float32)
    outer(x)
    assert not inner.unrun
    inner(x)
    report = obs.setup_report()
    assert _names(report) == ["first_step"]
    assert _stages(report, "first_step", "outer_fn")["lower"][0] == 1


def test_spans_of_two_threads_keep_their_own_parents():
    gate = threading.Barrier(2)

    def work(outer, inner):
        for _ in range(200):
            gate.wait()
            with obs.setup_span(outer):
                with obs.setup_span(inner):
                    pass

    threads = [threading.Thread(target=work, args=pair) for pair in
               (("model_build", "key_index"), ("state_init", "step_build"))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = obs.setup_report()["spans"]
    assert len(spans) == 800
    assert {s["path"] for s in spans} == {
        "model_build", "model_build/key_index",
        "state_init", "state_init/step_build"}


def test_trainer_run_emits_the_declared_spans():
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                n_heads=4, d_ff=64)
    trainer = Trainer(cfg, learning_rate=1e-2, warmup_steps=2,
                      decay_steps=100)
    state = trainer.init_state(jax.random.key(0))
    rng = np.random.default_rng(3)
    batches = [rng.integers(0, 64, (2, 16)).astype(np.int32)
               for _ in range(3)]
    state, losses = trainer.run(state, iter(batches))
    report = obs.setup_report()
    assert _names(report) == ["state_init", "step_build", "first_step"]
    assert set(_names(report)) <= set(catalog.SETUP_SPANS)
    step = _stages(report, "first_step", "train_step")
    assert {k: n for k, (n, _s) in step.items() if k != "trace"} in (
        {"lower": 1, "compile": 1}, {"lower": 1, "cache_read": 1})
    assert _stages(report, "state_init")["lower"][0] >= 1
    assert report["time_to_first_step_s"] > 0.0
    # a second run() opens nothing
    state, _ = trainer.run(state, iter(batches))
    assert _names(obs.setup_report()) == _names(report)


def test_a_retrace_after_the_first_step_is_booked_under_the_tracked_name():
    cat = obs_costs.get_catalog()
    cat.enabled, cat.memory, cat.path = True, False, None
    obs.set_enabled(True)
    f = obs_costs.track("toy_step", jax.jit(lambda x: (x * 2.0).sum()))
    f(jnp.ones((8,), jnp.float32))
    e = cat.entry("toy_step")
    assert (e["compiles"], e["retraces"]) == (1, 0)
    f(jnp.ones((8,), jnp.float32))                   # cached
    assert cat.entry("toy_step")["compiles"] == 1
    f(jnp.ones((12,), jnp.float32))                  # shape churn
    e = cat.entry("toy_step")
    assert (e["compiles"], e["retraces"]) == (2, 1)
    counters = obs.get_registry().snapshot()["counters"]
    assert counters["compile/retraces{fn=toy_step}"] == 1
    assert counters["compile/compiles{fn=toy_step}"] == 2
    # the steady state's retrace is no event of start-up's ledger
    assert _stages(obs.setup_report(), "first_step")["lower"][0] == 1
    assert not [r for r in obs.setup_report()["stages"]
                if r["span"] is None and not r["startup"]]


@pytest.fixture
def reader():
    sys.path.insert(0, REPO)
    try:
        from benchmark.readers import setup_ledger
        yield setup_ledger
    finally:
        sys.path.remove(REPO)


def test_the_reader_answers_none_without_a_setup_report(reader, monkeypatch):
    with obs.setup_span("model_build"):
        pass
    params = {"kind": "setup_ledger", "report": "seconds",
              "span": "^model_build$"}
    assert reader.read(params, {}) >= 0.0
    monkeypatch.delattr(obs, "setup_report")         # the parent commit
    for report in ("seconds", "stage_seconds", "time_to_first_step"):
        assert reader.read({**params, "report": report}, {}) is None


def test_the_reader_reads_spans_and_stages(reader):
    x = np.ones((6,), np.float32)        # no program of its own
    with obs.setup_span("model_build"):
        with obs.setup_span("key_index"):
            jax.jit(lambda x: x + 5.0)(x)
        jax.jit(lambda x: x + 6.0)(x)
    f = obs_costs.track("toy_step", jax.jit(lambda x: x * 5.0))

    def read(**params):
        return reader.read({"kind": "setup_ledger", **params}, {})

    # before the first step: no start-up to count to, no first_step span
    assert read(report="time_to_first_step") is None
    assert read(report="stage_seconds", span="^first_step$",
                stage="trace") is None
    f(x)
    spans = {s["name"]: s for s in obs.setup_report()["spans"]}
    assert read(report="time_to_first_step") == \
        obs.setup_report()["time_to_first_step_s"]
    assert read(report="seconds", span="^(model_build|state_init)$") == \
        spans["model_build"]["seconds"]
    assert read(report="seconds", span="^(model_build|key_index)$") == \
        spans["model_build"]["seconds"]          # the inner match: once
    assert read(report="seconds", span="^kernel_import$") == 0.0  # unopened
    # an event under key_index is under model_build too
    def own(path):
        return sum(r["seconds"] for r in obs.setup_report()["stages"]
                   if r["span"] == path)

    assert own("model_build") > 0.0 and own("model_build/key_index") > 0.0
    assert read(report="stage_seconds", span="^key_index$", stage=None) == \
        pytest.approx(own("model_build/key_index"))
    assert read(report="stage_seconds", stage=None,
                span="^(model_build|state_init|step_build)$") == \
        pytest.approx(own("model_build") + own("model_build/key_index"))
    stages = [read(report="stage_seconds", span="^first_step$", stage=s)
              for s in reader.STAGES]
    assert stages[0] > 0.0 and stages[1] > 0.0
    assert (stages[2] > 0.0) != (stages[3] > 0.0)    # compiled, or read
    assert sum(stages) == pytest.approx(read(
        report="stage_seconds", span="^first_step$", stage=None))
    assert sum(stages) <= spans["first_step"]["seconds"]
    with pytest.raises(ValueError, match="unknown report"):
        read(report="nope")
