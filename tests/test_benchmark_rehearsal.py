"""Tier-1's share of the yardstick: every cell of ``BENCHMARK.json``
rehearsed on the CPU, and the program names the harness reads.

``benchmark/run.py`` is what the driver measures every PR with, on the
chip; nothing else under ``tests/`` runs it.  Two guards:

* ``test_cell_rehearses_on_cpu[<cell>]`` — one case per ``workloads``
  entry, read from ``BENCHMARK.json`` (a later cell gets its case with
  no edit): the cell's own path at its toy size through
  ``--rehearse-cpu``, first step held to the plain reference, on
  ``chips`` virtual devices.  Counts and correctness only: a CPU run
  prints no time, rate, peak or share (``benchmark/README.md`` "A CPU
  rehearsal").
* ``test_harness_surface[<name>]`` — one case per line of
  ``benchmark/README.md`` "What the harness touches in the program".
  That README section is this test's specification; the test imports
  nothing from ``benchmark/`` and reads every name from the program, on
  toy models built by the harness's own call sequence (conf file ->
  ``global_config().load_conf().parse()`` -> ``Word2Vec(seed=)`` ->
  ``build_from_vocab`` -> ``train(batcher=, niters=1)``).  A PR that
  renames one of these learns it here, not from the driver's chip run.
"""

import ast
import contextlib
import inspect
import json
import math
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN = [sys.executable, os.path.join(REPO, "benchmark", "run.py")]

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = {w["name"]: w for w in BENCHMARK["workloads"]}


ARGS = ["--seed", "11", "--seconds", "2", "--trace", "0"]


def run_child(argv, tmp_path):
    """A process of its own on the CPU backend, without the suite's
    XLA_FLAGS: the harness makes ``chips`` virtual devices only when
    XLA_FLAGS names no count, and conftest.py names eight."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    return subprocess.run(argv, capture_output=True, text=True, timeout=170,
                          env=env, cwd=str(tmp_path))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_rehearses_on_cpu(cell, tmp_path):
    res = run_child(RUN + ["--workload", cell, *ARGS, "--rehearse-cpu"],
                    tmp_path)
    assert res.returncode == 0, res.stderr[-3000:]
    result = json.loads(res.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, res.stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == CELLS[cell]["chips"]
    # a CPU run may say what the loss is, never how fast or how full
    assert set(result["metrics"]) == {"train_loss_fixed"}
    assert math.isfinite(result["metrics"]["train_loss_fixed"]["value"])


def _one_cell_a_family() -> dict:
    """``{family: its first cell}``, read from the configurations' files."""
    out = {}
    files = {c["name"]: c["file"] for c in BENCHMARK["configs"]}
    for w in BENCHMARK["workloads"]:
        with open(os.path.join(REPO, files[w["config"]])) as f:
            out.setdefault(json.load(f)["family"], w["name"])
    return out


def _setup_ledger_entries(cell) -> set:
    """The ``per_layer`` entries the ``setup_ledger`` reader reads in
    ``cell`` (ISSUE 52's ``entry.*``), from the metric files."""
    out = set()
    for m in BENCHMARK["per_layer"]:
        with open(os.path.join(REPO, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            kind = json.load(f)["reader"]["kind"]
        if kind == "setup_ledger" and cell in m.get("workloads", [cell]):
            out.add(m["name"])
    return out


@pytest.mark.parametrize("family, cell", sorted(_one_cell_a_family().items()))
def test_traced_rehearsal_reads_the_setup_ledger(family, cell, tmp_path):
    """Each family's ``--rehearse-cpu --trace 1`` run shows a reading for
    every ``setup_ledger`` entry on its cell's list: the program keeps its
    start-up ledger in the harness's process, spans and compile events
    both, whichever model the family builds.  (The kernel's import reads
    0 seconds: a CPU process lowers no Pallas kernel.)"""
    res = run_child(RUN + ["--workload", cell, "--seed", "11", "--seconds",
                           "2", "--trace", "1", "--rehearse-cpu"], tmp_path)
    assert res.returncode == 0, res.stderr[-3000:]
    line = next(ln for ln in res.stdout.splitlines()
                if ln.startswith("[bench] rehearsal readings, not metrics: "))
    readings = ast.literal_eval(line.split("not metrics: ", 1)[1])
    want = _setup_ledger_entries(cell)
    assert len(want) == 6 and want <= set(readings), want - set(readings)
    got = {k: readings[k]["value"] for k in want}
    assert all(math.isfinite(v) and v >= 0.0 for v in got.values()), got
    assert got["entry.kernel_import_s"] == 0.0
    stages = [got[f"entry.step_{s}_s"] for s in ("trace", "lower")]
    assert stages[0] > 0.0 and stages[1] > 0.0
    assert sum(stages) + got["entry.step_compile_s"] + \
        got["entry.small_programs_s"] < got["entry.time_to_first_step_s"]
    assert got["entry.small_programs_s"] > 0.0
    # the run says the ledger on one line of the program's log
    assert sum(" start-up: " in ln for ln in res.stderr.splitlines()) == 1


def test_run_without_tpu_prints_no_result(tmp_path):
    """No TPU and no ``--rehearse-cpu``: non-zero exit, no result line,
    and no child process started (the chip belongs to one process —
    nothing on the measurement path may spawn another)."""
    marker = tmp_path / "spawned"
    prog = (
        "import runpy, subprocess, sys\n"
        "def boom(*a, **k):\n"
        f"    open({str(marker)!r}, 'w').close()\n"
        "    raise AssertionError('benchmark/run.py started a process')\n"
        "subprocess.Popen = boom\n"
        f"sys.argv = {[RUN[1], '--workload', sorted(CELLS)[0], *ARGS]!r}\n"
        f"runpy.run_path({RUN[1]!r}, run_name='__main__')\n")
    res = run_child([sys.executable, "-c", prog], tmp_path)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in res.stdout.splitlines()), res.stdout
    assert not marker.exists()


# -- what the harness touches in the program -----------------------------------

V, WINDOW, NEGATIVE, LEN_VEC, MINIBATCH = 300, 3, 4, 16, 4096
FIELDS = {"h", "v", "h2sum", "v2sum"}
#: ... and, since ISSUE 44, the call's tally behind the key: optional, so
#: the positions the harness's tool passes (up to the key) still lower
STEP_PARAMS = ("state", "slot_of_vocab", "alias_prob", "alias_idx",
               "centers", "contexts", "ctx_mask", "key", "tally")
#: a CBOW model's step takes the span batch, one packed buffer, and is
#: told the centers to cut it by
SPAN_STEP_PARAMS = ("state", "slot_of_vocab", "alias_prob", "alias_idx",
                    "span", "key", "tally", "centers")


class TwoBatches:
    """The batcher ``train()`` is handed: the native batcher's first two
    full batches of an epoch, in the rendering the model asks for (as
    the harness's ``ChunkBatcher`` forwards both), and a note of every
    size asked for."""

    def __init__(self, inner):
        self.inner, self.vocab = inner, inner.vocab
        self.asked, self.batches, self.kinds = [], [], []

    def _two_full(self, kind, batch_size):
        self.asked.append(batch_size)
        self.kinds.append(kind)
        gen = iter(getattr(self.inner, kind)(batch_size))
        try:
            for batch in gen:
                if batch.n_words == batch_size:
                    self.batches.append(batch)
                    yield batch
                if len(self.batches) == 2:
                    return
        finally:
            gen.close()           # stops the native prefetch thread

    def epoch(self, batch_size):
        return self._two_full("epoch", batch_size)

    def epoch_stencil(self, batch_size):
        return self._two_full("epoch_stencil", batch_size)


@contextlib.contextmanager
def span_parents():
    """``(name, enclosing span's name or None)`` of every ``obs.span`` the
    calling thread opens inside the block with telemetry on, in order."""
    from swiftmpi_tpu import obs

    opened, stack, real = [], [], obs.span

    class Recorded:
        def __init__(self, name, inner):
            self.name, self.inner = name, inner

        def __enter__(self):
            opened.append((self.name, stack[-1] if stack else None))
            stack.append(self.name)
            self.inner.__enter__()
            return self.inner

        def __exit__(self, *exc):
            stack.pop()
            return self.inner.__exit__(*exc)

    def span(name, **attrs):
        inner = real(name, **attrs)
        # telemetry off: the shared no-op, which a process's first
        # train() abandons unclosed when it arms the plane
        return Recorded(name, inner) if obs.get_registry().enabled \
            else inner

    obs.span = span
    try:
        yield opened
    finally:
        obs.span = real


def build_toy(sg, workdir):
    """A toy model by the harness's own call sequence, trained one call
    with telemetry on (the traced run's conf)."""
    from swiftmpi_tpu import obs
    from swiftmpi_tpu.data import native
    from swiftmpi_tpu.data.text import Vocab
    from swiftmpi_tpu.models.word2vec import Word2Vec
    from swiftmpi_tpu.utils import global_config, reset_global_config

    conf = os.path.join(workdir, f"cell_sg{sg}.conf")
    with open(conf, "w") as f:
        f.write(f"[word2vec]\nlen_vec: {LEN_VEC}\nwindow: {WINDOW}\n"
                f"negative: {NEGATIVE}\nsg: {sg}\nlearning_rate: 0.05\n"
                "sample: 0.0001\n[server]\ninitial_learning_rate: 0.7\n"
                f"[worker]\nminibatch: {MINIBATCH}\ntelemetry: 1\n"
                "telemetry_path: "
                + os.path.join(workdir, f"telemetry_sg{sg}.jsonl") + "\n")
    reset_global_config()
    global_config().load_conf(conf).parse()
    model = Word2Vec(seed=5)

    rng = np.random.default_rng(29)
    keys = rng.permutation(10 * V)[:V].astype(np.uint64) + np.uint64(1)
    tokens = rng.integers(0, V, 60_000).astype(np.int32)
    counts = np.bincount(tokens, minlength=V).astype(np.int64)
    order = np.lexsort((keys, -counts))      # count desc, key asc
    index_of = np.empty(V, np.int32)
    index_of[order] = np.arange(V, dtype=np.int32)
    vocab = Vocab(keys[order], counts[order],
                  dict(zip(keys[order].tolist(), range(V))))
    offsets = np.arange(0, len(tokens) + 1, 40, dtype=np.int64)
    model.build_from_vocab(vocab)
    stencil_at_build = model.stencil

    assert native.available(), "the native loader did not build"
    batcher = TwoBatches(native.PrefetchingCBOWBatcher(
        index_of[tokens], offsets, vocab, model.window, model.sample,
        seed=2013))
    key_before = np.asarray(jax.random.key_data(model._key))

    def span_counts():
        hists = obs.get_registry().snapshot()["hists"]
        return {k[len("phase_ms{phase="):-1]: h["count"]
                for k, h in hists.items() if k.startswith("phase_ms{phase=")}

    before = span_counts()        # the other toy's, if one test built both
    with span_parents() as parents:
        losses = model.train(batcher=batcher, niters=1)
    jax.block_until_ready(model.table.state)
    spans = {k: n - before.get(k, 0) for k, n in span_counts().items()}
    return SimpleNamespace(model=model, vocab=vocab, batcher=batcher,
                           key_before=key_before, losses=losses,
                           spans=spans, parents=parents,
                           stencil_at_build=stencil_at_build)


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """``toy(sg)``: the CBOW or the skip-gram toy, built once a module."""
    workdir, built = str(tmp_path_factory.mktemp("surface")), {}

    def get(sg=0):
        if sg not in built:
            built[sg] = build_toy(sg, workdir)
        return built[sg]
    return get


SURFACE = {}


def surface(case):
    SURFACE[case.__name__] = case
    return case


@surface
def conf_sequence(toy):
    """``global_config()`` / ``reset_global_config()`` /
    ``load_conf().parse()``, the conf keys written, and
    ``ensure_compile_cache()``."""
    from swiftmpi_tpu.utils import ConfigParser, global_config
    from swiftmpi_tpu.utils.xla_env import ensure_compile_cache

    assert isinstance(global_config(), ConfigParser)
    m = toy().model
    assert (m.len_vec, m.window, m.negative, m.sg) == \
        (LEN_VEC, WINDOW, NEGATIVE, 0)
    assert (m.alpha, m.sample, m.minibatch) == (0.05, 0.0001, MINIBATCH)
    assert m.config.get("server", "initial_learning_rate").to_float() == 0.7
    assert m.config.get("worker", "telemetry").to_bool()
    assert toy(1).model.sg == 1
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert ensure_compile_cache() == "/somewhere/else"


@surface
def vocab_and_batcher(toy):
    """``Vocab(keys, counts, index)``, ``native.available()``,
    ``PrefetchingCBOWBatcher(tokens, offsets, vocab, window, sample,
    seed=)`` with ``.epoch`` / ``.epoch_stencil`` / ``.vocab``, and the
    batch fields."""
    from swiftmpi_tpu.data import native
    from swiftmpi_tpu.data.text import Vocab

    assert [f.name for f in Vocab.__dataclass_fields__.values()] == \
        ["keys", "counts", "index"]
    params = list(inspect.signature(
        native.NativeCBOWBatcher.__init__).parameters)
    assert params == ["self", "tokens", "offsets", "vocab", "window",
                      "sample", "seed"]
    t = toy()
    inner = t.batcher.inner
    assert isinstance(inner, native.PrefetchingCBOWBatcher)
    assert inner.vocab is t.vocab
    assert callable(inner.epoch) and callable(inner.epoch_stencil)
    # skip-gram is fed per-pair batches ...
    batch = toy(1).batcher.batches[0]
    B = toy(1).batcher.asked[0]
    assert toy(1).batcher.kinds == ["epoch"]
    assert batch.centers.shape == (B,)
    assert batch.contexts.shape == batch.ctx_mask.shape == (B, 2 * WINDOW)
    assert batch.n_words == B
    # ... a CBOW model spans: full batches of B centers under the
    # cell's subsampling, in a span sized for them
    assert t.batcher.kinds == ["epoch_stencil"]
    from swiftmpi_tpu.data.text import span_positions
    assert 0.1 < inner.keep_mean < 0.5       # the toy's gate bites hard
    for batch in t.batcher.batches:
        S = batch.span
        assert S == span_positions(B, WINDOW, inner.keep_mean)
        assert S % 128 == 0 and S >= B / inner.keep_mean + 2 * WINDOW
        assert batch.tokens.shape == batch.sent_id.shape == (S,)
        assert batch.center_pos.shape == batch.half.shape == (B,)
        assert batch.n_words == B and (batch.center_pos >= 0).all()
        assert batch.pack().shape == (2 * S + 2 * B,)


@surface
def train_call(toy):
    """``train(batcher=, niters=1)``: one loss an iteration, the batch
    size it asks the batcher for, and ``.window .sample .stencil`` —
    the last truthy from ``build_from_vocab`` on for a CBOW model (the
    harness reads it before the first ``train()`` to pick the batches it
    peeks), never for skip-gram."""
    t = toy()
    assert len(t.losses) == 1 and math.isfinite(float(t.losses[0]))
    assert t.batcher.asked == [max(256, MINIBATCH // (2 * WINDOW))]
    assert len(t.batcher.batches) == 2
    m = t.model
    assert (m.window, m.sample, m.stencil) == (WINDOW, 0.0001, 1)
    assert t.stencil_at_build == 1
    assert (toy(1).model.stencil, toy(1).stencil_at_build) == (0, 0)


@surface
def table_state(toy):
    """``.table.state``: a dict of plain ``jax.Array``s by field, each of
    the logical shape ``(table.capacity, len_vec)``."""
    for sg in (0, 1):
        table = toy(sg).model.table
        assert type(table.state) is dict
        assert set(table.state) == FIELDS
        for field, a in table.state.items():
            assert isinstance(a, jax.Array), field
            assert a.shape == (table.capacity, LEN_VEC), field
            assert a.dtype == np.float32, field
        assert table.capacity >= V


@surface
def key_index_lookup(toy):
    """``.table.key_index.lookup(keys)``: one slot a key, inside the
    table, and the rows train() moved are among them."""
    t = toy()
    table = t.model.table
    slots = np.asarray(table.key_index.lookup(t.vocab.keys))
    assert slots.shape == (V,) and len(set(slots.tolist())) == V
    assert slots.min() >= 0 and slots.max() < table.capacity
    free = np.ones(table.capacity, bool)
    free[slots] = False
    v2sum = np.asarray(table.state["v2sum"])
    first = t.batcher.batches[0]
    trained = first.tokens[first.sent_id >= 0]
    assert (v2sum[slots[trained]] != v2sum[np.flatnonzero(free)[0]]).any()


@surface
def cluster_mesh(toy):
    """``.cluster.mesh`` and ``.cluster.table_axis``: the rows of every
    field split evenly over that axis."""
    m = toy().model
    shards = int(m.cluster.mesh.shape[m.cluster.table_axis])
    assert shards == len(jax.devices())
    for a in m.table.state.values():
        assert {s.data.shape[0] for s in a.addressable_shards} == \
            {a.shape[0] // shards}


@surface
def sampler_privates(toy):
    """``_key`` (a step keeps ``split(key)[0]`` for the next and draws
    with ``split(key)[1]``, as ``train()`` did on the host before ISSUE
    44), ``_alias_prob`` / ``_alias_idx`` (unigram^0.75 of the counts)
    and ``ops.sampling.sample_alias``."""
    from swiftmpi_tpu.ops.sampling import sample_alias

    t = toy()
    m = t.model
    key = jax.random.wrap_key_data(t.key_before)
    for _ in t.batcher.batches:              # one split a step
        key = jax.random.split(key)[0]
    assert np.array_equal(jax.random.key_data(m._key),
                          jax.random.key_data(key))
    prob = np.asarray(m._alias_prob, np.float64)
    alias = np.asarray(m._alias_idx)
    assert prob.shape == alias.shape == (V,)
    p = (prob + np.bincount(alias, 1.0 - prob, minlength=V)) / V
    want = t.vocab.counts.astype(np.float64) ** 0.75
    assert np.abs(p - want / want.sum()).max() < 1e-6
    draws = np.asarray(sample_alias(jax.random.split(m._key)[1],
                                    m._alias_prob, m._alias_idx,
                                    (8, NEGATIVE)))
    assert draws.shape == (8, NEGATIVE)
    assert draws.min() >= 0 and draws.max() < V


@surface
def sampling_state(toy):
    """``Word2Vec.sampling_state()``: the public face of the three names
    above (ISSUE 35; the harness switches to it in a ``benchmark`` issue).
    It agrees with them and leaves the key stream where it was."""
    m = toy().model
    before = np.asarray(jax.random.key_data(m._key))
    step_key, prob, alias = m.sampling_state()
    assert np.array_equal(jax.random.key_data(step_key),
                          jax.random.key_data(jax.random.split(m._key)[1]))
    assert prob is m._alias_prob and alias is m._alias_idx
    assert np.array_equal(jax.random.key_data(m._key), before)


@surface
def build_step_signature(toy):
    """``_build_step()``: the parameters a step is lowered by, and
    ``.lower``.  Skip-gram's are the per-pair positions the harness's
    ``tools/compile_real_size.py`` passes; a CBOW model's step takes the
    span (the tool still passes the per-pair ones: PERF.md section 7)."""
    for sg, params in ((0, SPAN_STEP_PARAMS), (1, STEP_PARAMS)):
        step = toy(sg).model._build_step()
        signature = inspect.signature(step).parameters
        assert tuple(signature) == params
        assert signature["tally"].default is None
        assert callable(step.lower)


@surface
def resolved_rendering(toy):
    """``resolved_rendering`` under the two configurations' keys: the
    CBOW step by span position and the per-pair skip-gram step."""
    assert toy(0).model.resolved_rendering == "stencil"
    assert toy(1).model.resolved_rendering == "sg"


@surface
def train_metrics(toy):
    """The ``train_metrics`` keys the ``train_metrics`` reader takes."""
    from swiftmpi_tpu.data.text import stencil_to_cbow

    for sg in (0, 1):
        t = toy(sg)
        metrics = t.model.train_metrics
        for key in ("stall_ms_per_step", "pairs_per_step",
                    "pair_fill_share", "rows_written_per_step",
                    "tiles_written_per_step", "tile_copies_per_step"):
            assert isinstance(metrics[key], (int, float)), (sg, key)
            assert math.isfinite(metrics[key]), (sg, key)
        # at most every slot of the step's two pushes a new row, each
        # written to a parameter and its accumulator
        masks = [b.ctx_mask if sg else
                 stencil_to_cbow(b, t.model.window).ctx_mask
                 for b in t.batcher.batches]
        B = len(masks[0])
        slots = masks[0].size + B * (1 + t.model.negative)
        assert 0 < metrics["rows_written_per_step"] <= 2 * slots * (
            2 * t.model.window if sg else 1)
        # a span batch's pairs are its expansion's, over the same grid
        assert metrics["pairs_per_step"] == pytest.approx(
            np.mean([m.sum() for m in masks]))
        assert metrics["pair_fill_share"] == pytest.approx(
            100 * np.mean([m.mean() for m in masks]))
        assert ("span_rows_per_step" in metrics) == (not sg)
    spans = [b.span for b in toy(0).batcher.batches]
    assert toy(0).model.train_metrics["span_rows_per_step"] == \
        np.mean(spans)


def loop_spans_cover_a_call(spans, parents, steps):
    """The spans of ISSUE 35, in either loop: ``step_prep``, ``dispatch``
    and ``step_book`` once a step and siblings of ``h2d``; ``loss_wait``
    once a call, inside ``loss_fetch``; no other nesting but the
    harness-visible ``input_wait`` inside ``train_setup`` (``Trainer.run``
    closes its set-up after the first ``next``)."""
    from swiftmpi_tpu.obs.catalog import HOST_SPANS

    assert {"step_prep", "step_book", "loss_wait"} <= set(HOST_SPANS)
    assert {name for name, _ in parents} <= set(HOST_SPANS)
    assert spans["step_prep"] == spans["step_book"] == spans["dispatch"] \
        == steps
    assert spans["loss_wait"] == spans["loss_fetch"] == 1
    inside = {name: {p for n, p in parents if n == name}
              for name, _ in parents}
    assert inside["loss_wait"] == {"loss_fetch"}
    for name in ("step_prep", "h2d", "dispatch", "step_book",
                 "loss_fetch", "train_finish", "train_setup"):
        assert inside[name] == {None}, (name, inside[name])
    assert inside["input_wait"] <= {None, "train_setup"}
    # an item of the loop, in order: the wait, then the four siblings
    loop = [n for n, _ in parents if n in
            ("input_wait", "step_prep", "h2d", "dispatch", "step_book")]
    item = ["input_wait", "step_prep", "h2d", "dispatch", "step_book"]
    assert loop[:len(item) * steps] == item * steps


@surface
def host_spans(toy):
    """The span names the harness credits device idle gaps to: declared,
    and (``render`` is the input pipeline's, off in every cell) opened
    once a step by the ``train()`` call the cells make."""
    from swiftmpi_tpu.obs.catalog import HOST_SPANS

    assert {"dispatch", "render", "h2d", "input_wait"} <= set(HOST_SPANS)
    t = toy()
    steps = len(t.batcher.batches)
    assert t.spans["dispatch"] == t.spans["h2d"] == steps
    assert t.spans["input_wait"] >= steps
    loop_spans_cover_a_call(t.spans, t.parents, steps)


@surface
def compile_tool(toy):
    """``tools/compile_real_size.py`` only: ``Cluster(config, devices=)``,
    ``Cluster.create_table`` and ``SparseTable._init_state``."""
    from swiftmpi_tpu.cluster.cluster import Cluster
    from swiftmpi_tpu.parameter.sparse_table import SparseTable

    assert list(inspect.signature(Cluster.__init__).parameters)[:3] == \
        ["self", "config", "devices"]
    assert list(inspect.signature(Cluster.create_table).parameters)[:4] \
        == ["self", "name", "access", "capacity_per_shard"]
    assert list(inspect.signature(SparseTable._init_state).parameters) == \
        ["self"]
    m = toy().model
    assert set(m.access.fields) == FIELDS
    assert m.table.key_index.capacity == m.table.capacity


# -- the language-model family's surface (benchmark/families/lm.py's head) ------

_LM = {}


def lm_toy():
    """A toy block stack trained one ``Trainer.run`` of two host batches
    with telemetry on, by the LM family's call sequence; built once."""
    if _LM:
        return _LM["toy"]
    import jax.numpy as jnp

    from swiftmpi_tpu import obs
    from swiftmpi_tpu.models.trainer import Trainer
    from swiftmpi_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=3, n_heads=4, n_kv_heads=1,
        d_ff=48, d_expert=16, max_seq=16, attention="blockwise",
        attn_block=8, loss_chunk=16, remat=True, remat_policy="full",
        n_experts=32, moe_top_k=4, experts_held=(0, 8),
        router="sigmoid_bias", expert_gated=True, qk_norm=True,
        layer_ops=("conv", "attention", "conv"),
        layer_ffns=("dense", "moe", "moe"), conv_kernel=3, norm_eps=1e-5,
        rope_base=1e6, init_std=0.3, matmul_dtype=jnp.bfloat16)
    was_on = obs.get_registry().enabled
    obs.set_enabled(True)

    def span_counts():
        hists = obs.get_registry().snapshot()["hists"]
        return {k[len("phase_ms{phase="):-1]: h["count"]
                for k, h in hists.items() if k.startswith("phase_ms{phase=")}

    trainer = Trainer(cfg, optimizer="adamw", aux_weight=0.0,
                      learning_rate=3e-4, warmup_steps=2, decay_steps=100,
                      weight_decay=0.1, grad_clip=1.0, b1=0.9, b2=0.95)
    state0 = trainer.init_state(jax.random.key(3))
    bias0 = [np.asarray(g["moe"].bias) for g in state0.params["blocks"]
             if "moe" in g]
    rng = np.random.default_rng(31)
    batches = [rng.integers(0, 64, (2, 16)).astype(np.int32)
               for _ in range(2)]
    before = span_counts()
    with span_parents() as parents:
        state, losses = trainer.run(state0, iter(batches))
    spans = {k: n - before.get(k, 0) for k, n in span_counts().items()}
    phase_map = obs.costs.phase_map("trainer_step")
    obs.set_enabled(was_on)
    _LM["toy"] = SimpleNamespace(cfg=cfg, trainer=trainer, state=state,
                                 losses=losses, spans=spans,
                                 parents=parents, bias0=bias0,
                                 batches=batches, phase_map=phase_map)
    return _LM["toy"]


@surface
def lm_trainer_sequence(toy):
    """``TransformerConfig`` fields, ``Trainer(cfg, **optimizer)``,
    ``init_state(key)``, ``run(state, batches) -> (state, losses)``,
    ``TrainState.params`` / ``.opt_state[1][0].mu``, ``hidden_states``."""
    from swiftmpi_tpu.models.transformer import hidden_states

    t = lm_toy()
    assert len(t.losses) == 2
    assert all(math.isfinite(float(x)) for x in t.losses)
    params = t.state.params
    assert set(params) == {"embed", "blocks", "ln_f"}
    assert [k for k, _n in t.cfg.layer_groups()] == [
        ("conv", "dense"), ("attention", "moe"), ("conv", "moe")]
    assert len(params["blocks"]) == 3
    mu = t.state.opt_state[1][0].mu
    assert jax.tree.structure(mu) == jax.tree.structure(params)
    hs = hidden_states(params, t.batches[0], t.cfg)
    assert len(hs) == 2 * t.cfg.n_layers + 1 and hs[0].shape == (2, 16, 32)
    # the selection bias is a buffer: a run leaves it bit-identical
    bias = [np.asarray(g["moe"].bias) for g in params["blocks"]
            if "moe" in g]
    assert all(np.array_equal(a, b) for a, b in zip(bias, t.bias0))
    assert any(np.abs(b).max() > 0 for b in bias)


@surface
def lm_host_spans(toy):
    """``Trainer.run`` opens ``train_setup``, ``loss_fetch`` and
    ``train_finish`` once a call and ``input_wait``, ``h2d`` and
    ``dispatch`` once a step, as ``Word2Vec.train`` does."""
    spans = lm_toy().spans
    assert spans["train_setup"] == spans["loss_fetch"] == \
        spans["train_finish"] == 1
    assert spans["input_wait"] == spans["h2d"] == spans["dispatch"] == 2
    loop_spans_cover_a_call(spans, lm_toy().parents, 2)


@surface
def lm_counters(toy):
    """``Trainer.train_metrics``: the stall split always, the expert
    layers' counters with telemetry on."""
    m = lm_toy().trainer.train_metrics
    assert m["steps"] == 2 and m["stall_ms_per_step"] >= 0.0
    assert 0.0 < m["held_pick_share"] <= 100.0
    assert m["expert_load_max_over_mean"] >= 1.0
    assert m["dropped_picks_per_step"] == 0.0


@surface
def lm_phase_map(toy):
    """``obs.costs.phase_map("trainer_step")``: the compiled step's
    instructions under the scopes the model enters, under the program's
    own name (every LM family's metric files say ``trainer_step``; a name
    no program of the process was tracked under has no map)."""
    from swiftmpi_tpu import obs
    from swiftmpi_tpu.obs.catalog import DEVICE_SCOPES

    pm = lm_toy().phase_map
    assert obs.costs.phase_map("w2v_step_of_no_model") is None
    assert pm["module"] == "jit_train_step"
    want = {"embed", "conv", "attention", "route", "experts", "dense_ffn",
            "head", "optimizer"}
    assert want <= set(DEVICE_SCOPES.values())
    assert want <= set(pm["phase"].values())


# -- the block-diffusion family's surface (benchmark/families/bdlm.py's head) ----

_BD = {}


def bd_toy():
    """A toy block stack trained by block diffusion, one ``Trainer.run`` of
    two host batches with telemetry on, by the family's call sequence."""
    if _BD:
        return _BD["toy"]
    from swiftmpi_tpu import obs
    from swiftmpi_tpu.models.trainer import Trainer
    from swiftmpi_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=8, n_kv_heads=1,
        d_head=8, d_expert=16, max_seq=32, attention="blockwise",
        attn_block=8, loss_chunk=16, remat=True, remat_policy="full",
        n_experts=128, moe_top_k=8, experts_held=(16, 32), router="softmax",
        expert_gated=True, qk_norm=True, layer_ops=("attention",) * 2,
        layer_ffns=("moe",) * 2, norm_eps=1e-6, rope_base=1e6, init_std=0.3,
        tied_head=False, objective="block_diffusion", diffusion_block=4,
        mask_token=63, noise_eps=1e-3)
    was_on = obs.get_registry().enabled
    obs.set_enabled(True)
    trainer = Trainer(cfg, optimizer="adamw", aux_weight=0.0,
                      learning_rate=3e-4, warmup_steps=2, decay_steps=100,
                      weight_decay=0.1, grad_clip=1.0, b1=0.9, b2=0.95)
    state0 = trainer.init_state(jax.random.key(3))
    rng = np.random.default_rng(37)
    batches = [rng.integers(0, 63, (2, 16)).astype(np.int32)
               for _ in range(2)]
    state, losses = trainer.run(state0, iter(batches))
    phase_map = obs.costs.phase_map("trainer_step")
    obs.set_enabled(was_on)
    _BD["toy"] = SimpleNamespace(cfg=cfg, trainer=trainer, state=state,
                                 losses=losses, batches=batches,
                                 phase_map=phase_map)
    return _BD["toy"]


@surface
def bdlm_config_and_tree(toy):
    """The ``TransformerConfig`` fields the family sets beyond the LM
    family's, ``params["head"]`` beside ``embed``, and AdamW's first moment
    shaped like them."""
    from swiftmpi_tpu.models.transformer import TransformerConfig

    fields = TransformerConfig.__dataclass_fields__
    for name, default in [("d_head", 0), ("tied_head", True),
                          ("objective", "next_token"),
                          ("diffusion_block", 4), ("mask_token", 0),
                          ("noise_eps", 1e-3)]:
        assert fields[name].default == default, name
    t = bd_toy()
    assert t.cfg.head_dim == 8 != t.cfg.d_model // t.cfg.n_heads
    params = t.state.params
    assert set(params) == {"embed", "head", "blocks", "ln_f"}
    assert params["head"].shape == params["embed"].shape == (64, 32)
    assert len(params["blocks"]) == 1            # one run of equal layers
    assert params["blocks"][0]["wq"].shape == (2, 32, 64)
    mu = t.state.opt_state[1][0].mu
    assert jax.tree.structure(mu) == jax.tree.structure(params)
    assert len(t.losses) == 2
    assert all(math.isfinite(float(x)) for x in t.losses)


@surface
def bdlm_noise_drawn_again(toy):
    """``Trainer.noise_key(step)`` + ``diffusion.block_noise(key, tokens,
    cfg)``: the step's noise from outside the step, and ``trunk_input`` /
    ``attention_inputs`` / ``hidden_states`` on ``[noisy ; tokens]``."""
    from swiftmpi_tpu.models import diffusion
    from swiftmpi_tpu.models.transformer import hidden_states, lm_loss

    t = bd_toy()
    assert list(inspect.signature(diffusion.block_noise).parameters) == \
        ["key", "tokens", "cfg"]
    assert list(inspect.signature(t.trainer.noise_key).parameters) == ["step"]
    # the second step's loss, made again from its key on the state before it
    tr = type(t.trainer)(t.cfg, optimizer="adamw", aux_weight=0.0,
                         learning_rate=3e-4, warmup_steps=2, decay_steps=100,
                         weight_decay=0.1, grad_clip=1.0, b1=0.9, b2=0.95)
    state = tr.init_state(jax.random.key(3))
    state, _ = tr.step(state, t.batches[0])
    assert int(state.step) == 1
    again = lm_loss(state.params, t.batches[1], t.cfg, aux_weight=0.0,
                    noise_key=tr.noise_key(1))
    assert float(again) == pytest.approx(float(t.losses[1]), rel=1e-5)
    noisy, weights = diffusion.block_noise(tr.noise_key(1), t.batches[1],
                                           t.cfg)
    assert noisy.shape == weights.shape == (2, 16)
    assert ((np.asarray(noisy) == 63) == (np.asarray(weights) > 0)).all()
    z = diffusion.trunk_input(noisy, t.batches[1])
    assert z.shape == (2, 32)
    attn = diffusion.attention_inputs(16, t.cfg)
    assert sorted(attn) == ["mask", "positions"]
    assert list(np.asarray(attn["positions"])) == list(range(16)) * 2
    hs = hidden_states(state.params, z, t.cfg, **attn)
    assert len(hs) == 2 * t.cfg.n_layers + 1 and hs[0].shape == (2, 32, 32)
    # the layer does not know the objective: a plain call is a causal pass
    causal = hidden_states(state.params, z, t.cfg)
    assert not np.allclose(np.asarray(causal[-1]), np.asarray(hs[-1]))


@surface
def bdlm_phase_map_and_counters(toy):
    """The device scope ``noise`` beside the LM step's, and the expert
    layers' counters of a run whose share is not the first range."""
    from swiftmpi_tpu.obs.catalog import DEVICE_SCOPES

    t = bd_toy()
    want = {"noise", "embed", "attention", "route", "experts", "head",
            "optimizer"}
    assert want <= set(DEVICE_SCOPES.values())
    assert want <= set(t.phase_map["phase"].values())
    assert t.phase_map["module"] == "jit_train_step"
    m = t.trainer.train_metrics
    assert m["steps"] == 2 and m["dropped_picks_per_step"] == 0.0
    assert 0.0 < m["held_pick_share"] < 100.0
    assert m["expert_load_max_over_mean"] >= 1.0
    # a share's router is left alone
    router0 = t.trainer.init_state(jax.random.key(3)).params["blocks"][0][
        "moe"].router
    assert np.array_equal(np.asarray(router0), np.asarray(
        t.state.params["blocks"][0]["moe"].router))


# -- the sliding-window family's surface (benchmark/families/swlm.py's head) ------

_SW = {}


def sw_toy():
    """A toy stack of sliding and full attention layers over dense and
    expert FFNs, one ``Trainer.run`` of two host batches with telemetry on,
    by the family's call sequence."""
    if _SW:
        return _SW["toy"]
    from swiftmpi_tpu import obs
    from swiftmpi_tpu.models.trainer import Trainer
    from swiftmpi_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=4, n_heads=8, n_kv_heads=1,
        d_head=8, d_ff=48, d_expert=16, max_seq=32, attention="blockwise",
        attn_block=8, loss_chunk=16, remat=True, remat_policy="full",
        n_experts=128, moe_top_k=8, experts_held=(80, 88),
        router="sigmoid_bias", route_scale=2.826, n_shared_experts=1,
        expert_gated=True, qk_norm=True, attn_gate=True, sandwich_norm=True,
        layer_ops=("sliding", "sliding", "full", "sliding"),
        layer_ffns=("dense", "moe", "moe", "moe"), window=12,
        embed_scale=32 ** 0.5, norm_eps=1e-5, rope_base=1e4, init_std=0.3,
        tied_head=False)
    was_on = obs.get_registry().enabled
    obs.set_enabled(True)
    trainer = Trainer(cfg, optimizer="adamw", aux_weight=0.0,
                      learning_rate=3e-4, warmup_steps=2, decay_steps=100,
                      weight_decay=0.1, grad_clip=1.0, b1=0.9, b2=0.95)
    state0 = trainer.init_state(jax.random.key(3))
    rng = np.random.default_rng(41)
    batches = [rng.integers(0, 64, (1, 32)).astype(np.int32)
               for _ in range(2)]
    state, losses = trainer.run(state0, iter(batches))
    phase_map = obs.costs.phase_map("trainer_step")
    obs.set_enabled(was_on)
    _SW["toy"] = SimpleNamespace(cfg=cfg, trainer=trainer, state=state,
                                 losses=losses, batches=batches,
                                 phase_map=phase_map)
    return _SW["toy"]


@surface
def swlm_config_and_tree(toy):
    """The ``TransformerConfig`` fields and operator kinds the family sets
    beyond the other LM families', the parameter names it samples, and
    ``hidden_states`` at every half layer."""
    from swiftmpi_tpu.models.transformer import (ATTENTION_OPS, OPS,
                                                 TransformerConfig,
                                                 hidden_states)

    fields = TransformerConfig.__dataclass_fields__
    for name, default in [("window", 0), ("attn_gate", False),
                          ("sandwich_norm", False), ("embed_scale", 1.0),
                          ("n_shared_experts", 0), ("route_scale", 1.0)]:
        assert fields[name].default == default, name
    assert {"sliding", "full", "attention"} < set(ATTENTION_OPS) < set(OPS)
    t = sw_toy()
    assert [k for k, _n in t.cfg.layer_groups()] == [
        ("sliding", "dense"), ("sliding", "moe"), ("full", "moe"),
        ("sliding", "moe")]
    params = t.state.params
    assert set(params) == {"embed", "head", "blocks", "ln_f"}
    attn = {"ln1", "ln1_post", "ln2", "ln2_post", "wq", "wk", "wv", "wo",
            "wg", "q_norm", "k_norm"}
    assert set(params["blocks"][0]) == attn | {"w_gate", "w_up", "w_down"}
    assert set(params["blocks"][2]) == attn | {
        "moe", "shared_gate", "shared_up", "shared_down"}
    assert params["blocks"][1]["wg"].shape == (1, 32, 64)
    assert params["blocks"][1]["shared_down"].shape == (1, 16, 32)
    assert params["blocks"][1]["moe"].w_in.shape == (1, 8, 32, 16)
    mu = t.state.opt_state[1][0].mu
    assert jax.tree.structure(mu) == jax.tree.structure(params)
    assert len(t.losses) == 2
    assert all(math.isfinite(float(x)) for x in t.losses)
    hs = hidden_states(params, t.batches[0], t.cfg)
    assert len(hs) == 2 * t.cfg.n_layers + 1 and hs[0].shape == (1, 32, 32)


@surface
def swlm_masks_walked(toy):
    """``WindowMask(window)`` and ``CAUSAL`` with ``key_tiles(i, n, size)
    -> (lo, hi, tile)`` and ``visible(qa, kc)``, as the family walks them
    for its pair-fill counters."""
    from swiftmpi_tpu.parallel.ring_attention import CAUSAL, WindowMask

    t = sw_toy()
    size, n = t.cfg.attn_block, 32 // t.cfg.attn_block
    pos = np.arange(size)
    for mask, pairs in ((WindowMask(t.cfg.window), 12 * 13 // 2 + 20 * 12),
                        (CAUSAL, 32 * 33 // 2)):
        assert mask.tile(size, 32) == size
        seen = 0
        for i in range(n):
            lo, hi, tile = mask.key_tiles(i, n, size)
            for k in range(int(lo), int(hi)):
                j = int(tile(k))
                seen += int(np.asarray(mask.visible(
                    (i * size + pos)[:, None], (j * size + pos)[None])).sum())
        assert seen == pairs


@surface
def swlm_phase_map_and_counters(toy):
    """The device scopes ``window_attention`` and ``shared_expert`` beside
    the LM step's, the expert layers' counters, and a share's router and
    selection bias left alone."""
    from swiftmpi_tpu.obs.catalog import DEVICE_SCOPES

    t = sw_toy()
    want = {"embed", "window_attention", "attention", "route", "experts",
            "shared_expert", "dense_ffn", "head", "optimizer"}
    assert want <= set(DEVICE_SCOPES.values())
    assert want <= set(t.phase_map["phase"].values())
    assert t.phase_map["module"] == "jit_train_step"
    m = t.trainer.train_metrics
    assert m["steps"] == 2 and m["dropped_picks_per_step"] == 0.0
    assert 0.0 < m["held_pick_share"] < 100.0
    assert m["expert_load_max_over_mean"] >= 1.0
    state0 = t.trainer.init_state(jax.random.key(3))
    for before, after in zip(state0.params["blocks"][1:],
                             t.state.params["blocks"][1:]):
        for name in ("router", "bias"):
            assert np.array_equal(np.asarray(getattr(before["moe"], name)),
                                  np.asarray(getattr(after["moe"], name)))
    assert not np.array_equal(np.asarray(state0.params["blocks"][1]["wg"]),
                              np.asarray(t.state.params["blocks"][1]["wg"]))


# -- the latent-attention family's surface (benchmark/families/mlalm.py's head) ----

_MLA = {}


def mla_toy():
    """A toy stack of latent attention layers over a dense and expert FFNs
    with a multi-token-prediction module behind it, one ``Trainer.run`` of
    two host batches with telemetry on, by the family's call sequence."""
    if _MLA:
        return _MLA["toy"]
    from swiftmpi_tpu import obs
    from swiftmpi_tpu.models.trainer import Trainer
    from swiftmpi_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=3, n_heads=4, d_ff=160,
        d_expert=24, max_seq=32, attention="blockwise", attn_block=8,
        loss_chunk=16, remat=True, remat_policy="full", n_experts=64,
        moe_top_k=4, experts_held=(8, 16), router="sigmoid_bias",
        route_scale=1.8, n_shared_experts=1, expert_gated=True,
        layer_ops=("latent",) * 3, layer_ffns=("dense", "moe", "moe"),
        q_lora_rank=12, kv_lora_rank=8, qk_nope_dim=6, qk_rope_dim=2,
        v_head_dim=8, norm_eps=1e-5, rope_base=1e6, init_std=0.3,
        tied_head=False, mtp_layers=1, mtp_weight=0.3)
    was_on = obs.get_registry().enabled
    obs.set_enabled(True)
    trainer = Trainer(cfg, optimizer="adamw", aux_weight=0.0,
                      learning_rate=3e-4, warmup_steps=2, decay_steps=100,
                      weight_decay=0.1, grad_clip=1.0, b1=0.9, b2=0.95)
    state0 = trainer.init_state(jax.random.key(3))
    rng = np.random.default_rng(42)
    batches = [rng.integers(0, 64, (1, 32)).astype(np.int32)
               for _ in range(2)]
    state, losses = trainer.run(state0, iter(batches))
    phase_map = obs.costs.phase_map("trainer_step")
    obs.set_enabled(was_on)
    _MLA["toy"] = SimpleNamespace(cfg=cfg, trainer=trainer, state=state,
                                  losses=losses, batches=batches,
                                  phase_map=phase_map)
    return _MLA["toy"]


@surface
def mlalm_config_and_tree(toy):
    """The ``TransformerConfig`` fields and the operator kind the family
    sets beyond the other LM families', the parameter names it samples —
    the latent layer's and ``params["mtp"]`` — and ``hidden_states`` at
    every half layer and at the module's three states."""
    from swiftmpi_tpu.models.transformer import (ATTENTION_OPS, OPS,
                                                 TransformerConfig,
                                                 hidden_states)

    fields = TransformerConfig.__dataclass_fields__
    for name, default in [("q_lora_rank", 0), ("kv_lora_rank", 0),
                          ("qk_nope_dim", 0), ("qk_rope_dim", 0),
                          ("v_head_dim", 0), ("mtp_layers", 0),
                          ("mtp_weight", 0.3)]:
        assert fields[name].default == default, name
    assert "latent" in ATTENTION_OPS and set(ATTENTION_OPS) < set(OPS)
    t = mla_toy()
    assert t.cfg.layer_groups() == [(("latent", "dense"), 1),
                                    (("latent", "moe"), 2)]
    params = t.state.params
    assert set(params) == {"embed", "head", "blocks", "ln_f", "mtp"}
    latent = {"ln1", "ln2", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
              "wkv_b", "wo"}
    experts = {"moe", "shared_gate", "shared_up", "shared_down"}
    assert set(params["blocks"][0]) == latent | {"w_gate", "w_up", "w_down"}
    assert set(params["blocks"][1]) == latent | experts
    assert params["blocks"][1]["wkv_a"].shape == (2, 32, 8 + 2)
    assert params["blocks"][1]["wkv_b"].shape == (2, 8, 4 * (6 + 8))
    assert params["blocks"][1]["wq_b"].shape == (2, 12, 4 * 8)
    assert params["blocks"][1]["wo"].shape == (2, 4 * 8, 32)
    mtp = params["mtp"]
    assert set(mtp) == {"hnorm", "enorm", "eh_proj", "block", "norm"}
    assert mtp["eh_proj"].shape == (64, 32)
    assert set(mtp["block"]) == latent | experts
    assert mtp["block"]["moe"].w_in.shape == (1, 8, 32, 24)
    mu = t.state.opt_state[1][0].mu
    assert jax.tree.structure(mu) == jax.tree.structure(params)
    assert len(t.losses) == 2
    assert all(math.isfinite(float(x)) for x in t.losses)
    hs = hidden_states(params, t.batches[0], t.cfg)
    assert len(hs) == 2 * t.cfg.n_layers + 1 + 3
    assert all(h.shape == (1, 32, 32) for h in hs)


@surface
def mlalm_phase_map_and_counters(toy):
    """The device scopes ``latent_attention`` and ``mtp`` beside the LM
    step's, the loss's two parts and the module's expert counters in
    ``train_metrics``, one launch a step, and a share's routers and
    selection biases — the module's too — left alone."""
    from swiftmpi_tpu.obs.catalog import DEVICE_SCOPES, LAYER_SCOPES

    t = mla_toy()
    want = {"embed", "latent_attention", "mtp", "route", "experts",
            "shared_expert", "dense_ffn", "head", "optimizer"}
    assert want <= set(DEVICE_SCOPES.values())
    assert {DEVICE_SCOPES["mtp_" + s] for s in LAYER_SCOPES} == {"mtp"}
    assert want <= set(t.phase_map["phase"].values())
    assert not any(p.startswith("mtp_") for p in
                   t.phase_map["phase"].values())
    assert t.phase_map["module"] == "jit_train_step"
    m = t.trainer.train_metrics
    assert m["steps"] == 2 and m["dropped_picks_per_step"] == 0.0
    assert m["mtp_dropped_picks_per_step"] == 0.0
    assert 0.0 < m["held_pick_share"] < 100.0
    assert 0.0 <= m["mtp_held_pick_share"] <= 100.0
    mean = sum(float(x) for x in t.losses) / 2
    assert abs(m["main_loss"] + 0.3 * m["mtp_loss"] - mean) < 1e-4 * mean
    assert 0.0 < m["mtp_loss_share"] < 100.0
    state0 = t.trainer.init_state(jax.random.key(3))
    pairs = [(state0.params["blocks"][1], t.state.params["blocks"][1]),
             (state0.params["mtp"]["block"], t.state.params["mtp"]["block"])]
    for before, after in pairs:
        for name in ("router", "bias"):
            assert np.array_equal(np.asarray(getattr(before["moe"], name)),
                                  np.asarray(getattr(after["moe"], name)))
        assert not np.array_equal(np.asarray(before["wkv_a"]),
                                  np.asarray(after["wkv_a"]))
    assert not np.array_equal(np.asarray(state0.params["mtp"]["eh_proj"]),
                              np.asarray(t.state.params["mtp"]["eh_proj"]))


# -- the state-space hybrid family's surface (benchmark/families/sslm.py's head) ---

_SSL = {}


def ssl_toy():
    """A toy stack of half layers — state-space mixers, one attention layer
    with no position embedding, ungated squared-ReLU expert layers beside a
    wider shared expert — one ``Trainer.run`` of two host batches with
    telemetry on, by the family's call sequence."""
    if _SSL:
        return _SSL["toy"]
    from swiftmpi_tpu import obs
    from swiftmpi_tpu.models.trainer import Trainer
    from swiftmpi_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=5, n_heads=16, n_kv_heads=1,
        d_head=4, d_expert=12, max_seq=32, attention="blockwise",
        attn_block=8, loss_chunk=16, remat=True, remat_policy="full",
        n_experts=128, moe_top_k=6, experts_held=(8, 16),
        router="sigmoid_bias", route_scale=2.5, n_shared_experts=1,
        d_shared_expert=24, expert_gated=False, expert_act="relu2",
        layer_ops=("ssm", "none", "ssm", "full", "none"),
        layer_ffns=("none", "moe", "none", "none", "moe"),
        ssm_heads=8, ssm_head_dim=4, ssm_state=8, ssm_groups=1, ssm_conv=4,
        ssm_chunk=8, norm_eps=1e-5, init_std=0.3, tied_head=False)
    was_on = obs.get_registry().enabled
    obs.set_enabled(True)
    trainer = Trainer(cfg, optimizer="adamw", aux_weight=0.0,
                      learning_rate=3e-4, warmup_steps=2, decay_steps=100,
                      weight_decay=0.1, grad_clip=1.0, b1=0.9, b2=0.95)
    state0 = trainer.init_state(jax.random.key(3))
    rng = np.random.default_rng(46)
    batches = [rng.integers(0, 64, (1, 32)).astype(np.int32)
               for _ in range(2)]
    state, losses = trainer.run(state0, iter(batches))
    phase_map = obs.costs.phase_map("trainer_step")
    obs.set_enabled(was_on)
    _SSL["toy"] = SimpleNamespace(cfg=cfg, trainer=trainer, state=state,
                                  losses=losses, batches=batches,
                                  phase_map=phase_map)
    return _SSL["toy"]


@surface
def sslm_config_and_tree(toy):
    """The ``TransformerConfig`` fields and the kinds the family sets beyond
    the other LM families', the parameter names it samples — a mixer's, and
    an expert layer without gates — and ``hidden_states`` with an absent
    half repeating its input."""
    from swiftmpi_tpu.models.transformer import (FFNS, OPS,
                                                 TransformerConfig,
                                                 hidden_states)

    fields = TransformerConfig.__dataclass_fields__
    for name, default in [("ssm_heads", 0), ("ssm_head_dim", 0),
                          ("ssm_state", 0), ("ssm_groups", 0),
                          ("ssm_conv", 4), ("ssm_chunk", 128),
                          ("expert_act", "relu"), ("d_shared_expert", 0)]:
        assert fields[name].default == default, name
    assert {"ssm", "none", "full"} <= set(OPS) and "none" in FFNS
    t = ssl_toy()
    assert t.cfg.layer_groups() == [
        (("ssm", "none"), 1), (("none", "moe"), 1), (("ssm", "none"), 1),
        (("full", "none"), 1), (("none", "moe"), 1)]
    params = t.state.params
    assert set(params) == {"embed", "head", "blocks", "ln_f"}
    assert set(params["blocks"][0]) == {
        "ln1", "ssm_in", "ssm_out", "ssm_conv_w", "ssm_conv_b", "A_log",
        "dt_bias", "D", "ssm_norm"}
    assert set(params["blocks"][1]) == {"ln2", "moe", "shared_up",
                                        "shared_down"}
    assert set(params["blocks"][3]) == {"ln1", "wq", "wk", "wv", "wo"}
    assert params["blocks"][0]["ssm_in"].shape == (1, 32, 32 + 48 + 8)
    assert params["blocks"][0]["ssm_conv_w"].shape == (1, 4, 48)
    assert params["blocks"][0]["A_log"].shape == (1, 8)
    moe = params["blocks"][1]["moe"]
    assert moe.w_gate is None and moe.w_in.shape == (1, 8, 32, 12)
    assert params["blocks"][1]["shared_up"].shape == (1, 32, 24)
    mu = t.state.opt_state[1][0].mu
    assert jax.tree.structure(mu) == jax.tree.structure(params)
    assert len(t.losses) == 2
    assert all(math.isfinite(float(x)) for x in t.losses)
    hs = hidden_states(params, t.batches[0], t.cfg)
    assert len(hs) == 2 * t.cfg.n_layers + 1
    assert all(h.shape == (1, 32, 32) for h in hs)
    assert np.array_equal(hs[1], hs[2]) and np.array_equal(hs[2], hs[3])


@surface
def sslm_phase_map_and_counters(toy):
    """The device scopes ``ssm_mixer`` and ``ssm_scan`` beside the LM
    step's, ``ssm_scan_chunks`` beside the expert counters in
    ``train_metrics``, one program a step, and a share's routers and
    selection biases left alone while the mixer's own vectors move."""
    from swiftmpi_tpu.obs.catalog import DEVICE_SCOPES, LAYER_SCOPES

    t = ssl_toy()
    want = {"embed", "ssm_mixer", "ssm_scan", "attention", "route",
            "experts", "shared_expert", "head", "optimizer"}
    assert want <= set(DEVICE_SCOPES.values())
    assert {"ssm_mixer", "ssm_scan"} <= set(LAYER_SCOPES)
    assert want <= set(t.phase_map["phase"].values())
    assert t.phase_map["module"] == "jit_train_step"
    m = t.trainer.train_metrics
    assert m["steps"] == 2 and m["dropped_picks_per_step"] == 0.0
    # 1 sequence x 32 / 8 chunks x 2 state-space layers
    assert m["ssm_scan_chunks"] == 8.0
    assert 0.0 <= m["held_pick_share"] <= 100.0
    assert "main_loss" not in m and "mtp_loss_share" not in m
    state0 = t.trainer.init_state(jax.random.key(3))
    for i in (1, 4):
        before, after = state0.params["blocks"][i], t.state.params["blocks"][i]
        for name in ("router", "bias"):
            assert np.array_equal(np.asarray(getattr(before["moe"], name)),
                                  np.asarray(getattr(after["moe"], name)))
    for name in ("ssm_in", "ssm_conv_w", "ssm_conv_b", "A_log", "dt_bias",
                 "D", "ssm_norm"):
        assert not np.array_equal(
            np.asarray(state0.params["blocks"][0][name]),
            np.asarray(t.state.params["blocks"][0][name])), name


# -- the sparse-attention family's surface (benchmark/families/salm.py's head) ---

_SAL = {}


def sal_toy():
    """A toy stack of sparse-attention expert layers — an indexer of 2 heads
    of 4, top 8 of 32 positions — one ``Trainer.run`` of two host batches
    with telemetry on, by the family's call sequence."""
    if _SAL:
        return _SAL["toy"]
    from swiftmpi_tpu import obs
    from swiftmpi_tpu.models.trainer import Trainer
    from swiftmpi_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=8, n_kv_heads=1,
        d_head=4, d_expert=12, max_seq=32, attention="blockwise",
        attn_block=8, loss_chunk=16, remat=True, remat_policy="full",
        n_experts=128, moe_top_k=8, experts_held=(112, 128),
        router="softmax", expert_gated=True, qk_norm=True,
        layer_ops=("sparse",) * 2, layer_ffns=("moe",) * 2,
        index_heads=2, index_head_dim=4, index_topk=8,
        norm_eps=1e-6, rope_base=1e7, init_std=0.3, tied_head=False)
    was_on = obs.get_registry().enabled
    obs.set_enabled(True)
    trainer = Trainer(cfg, optimizer="adamw", aux_weight=0.0,
                      learning_rate=3e-4, warmup_steps=2, decay_steps=100,
                      weight_decay=0.1, grad_clip=1.0, b1=0.9, b2=0.95)
    state0 = trainer.init_state(jax.random.key(3))
    rng = np.random.default_rng(49)
    batches = [rng.integers(0, 64, (1, 32)).astype(np.int32)
               for _ in range(2)]
    state, losses = trainer.run(state0, iter(batches))
    phase_map = obs.costs.phase_map("trainer_step")
    obs.set_enabled(was_on)
    _SAL["toy"] = SimpleNamespace(cfg=cfg, trainer=trainer, state=state,
                                  losses=losses, batches=batches,
                                  phase_map=phase_map)
    return _SAL["toy"]


@surface
def salm_config_and_tree(toy):
    """The ``TransformerConfig`` fields and the kind the family sets beyond
    the other LM families', the parameter names it samples, and
    ``sparse_probe`` with ``index_tile`` / ``unpack``: what the selection
    check reads."""
    from swiftmpi_tpu.models.transformer import (OPS, TransformerConfig,
                                                 hidden_states, sparse_probe)
    from swiftmpi_tpu.parallel.sparse_attention import index_tile, unpack

    fields = TransformerConfig.__dataclass_fields__
    for name in ("index_heads", "index_head_dim", "index_topk"):
        assert fields[name].default == 0, name
    assert "sparse" in OPS
    t = sal_toy()
    assert t.cfg.layer_groups() == [(("sparse", "moe"), 2)]
    params = t.state.params
    assert set(params) == {"embed", "head", "blocks", "ln_f"}
    assert set(params["blocks"][0]) == {
        "ln1", "ln2", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "wq_idx",
        "wk_idx", "w_idx", "idx_ln_g", "idx_ln_b", "moe"}
    blk = jax.tree.map(lambda a: a[0], params["blocks"][0])
    assert blk["wq_idx"].shape == (32, 8) and blk["wk_idx"].shape == (32, 4)
    assert blk["w_idx"].shape == (32, 2) and blk["idx_ln_b"].shape == (4,)
    mu = t.state.opt_state[1][0].mu
    assert jax.tree.structure(mu) == jax.tree.structure(params)
    hs = hidden_states(params, t.batches[0], t.cfg)
    assert len(hs) == 5 and all(h.shape == (1, 32, 32) for h in hs)
    probe = sparse_probe(blk, hs[0], t.cfg)
    assert set(probe) == {"qi", "w", "ki", "bits", "index_loss", "kept"}
    assert probe["qi"].shape == (1, 32, 2, 4)
    assert probe["w"].shape == (1, 32, 2) and probe["ki"].shape == (1, 32, 4)
    keep = unpack(probe["bits"], 8)
    assert keep.shape == (1, 32, 32) and keep.dtype == bool
    # sum_t min(t + 1, 8) over 32 positions
    assert int(probe["kept"]) == int(keep.sum()) == 36 + 24 * 8
    scores = index_tile(probe["qi"], probe["w"], probe["ki"])
    assert scores.shape == (1, 32, 32) and scores.dtype == np.float32


@surface
def salm_phase_map_and_counters(toy):
    """The device scopes ``sparse_attention``, ``indexer`` and
    ``index_select`` beside the LM step's, the objective's two parts and the
    selection's counters beside the expert counters in ``train_metrics``,
    and a share's routers left alone while the indexer moves."""
    from swiftmpi_tpu.obs.catalog import DEVICE_SCOPES, LAYER_SCOPES

    t = sal_toy()
    want = {"embed", "sparse_attention", "indexer", "index_select", "route",
            "experts", "head", "optimizer"}
    assert want <= set(DEVICE_SCOPES.values())
    assert {"sparse_attention", "indexer", "index_select"} \
        <= set(LAYER_SCOPES)
    assert want <= set(t.phase_map["phase"].values())
    assert t.phase_map["module"] == "jit_train_step"
    m = t.trainer.train_metrics
    assert m["steps"] == 2 and m["dropped_picks_per_step"] == 0.0
    assert m["selected_keys_per_query"] == (36 + 24 * 8) / 32
    assert math.isclose(m["selected_pair_share"],
                        100 * (36 + 24 * 8) / (32 * 33 / 2), rel_tol=1e-6)
    assert math.isclose(m["index_loss_per_layer"], m["index_loss"] / 2,
                        rel_tol=1e-6)
    losses = [float(x) for x in t.losses]
    assert math.isclose(m["main_loss"] + m["index_loss"],
                        sum(losses) / 2, rel_tol=1e-5)
    assert "mtp_loss_share" not in m and "ssm_scan_chunks" not in m
    before = t.trainer.init_state(jax.random.key(3)).params["blocks"][0]
    after = t.state.params["blocks"][0]
    assert np.array_equal(np.asarray(before["moe"].router),
                          np.asarray(after["moe"].router))
    for name in ("wq_idx", "wk_idx", "w_idx", "idx_ln_g", "idx_ln_b", "wq"):
        assert not np.array_equal(np.asarray(before[name]),
                                  np.asarray(after[name])), name


@pytest.mark.parametrize("name", sorted(SURFACE))
def test_harness_surface(name, toy):
    SURFACE[name](toy)


@pytest.mark.parametrize("name, reads, err, ok", [
    # the objective the timed step returned has a limit of its own again
    ("loss", "loss", 5.9e-5, True),
    ("loss", "loss", 3.25e-4, False),       # a bf16 head softmax and loss
    # the step's own index loss reads the per-layer index loss's limit
    ("step.index_loss", "index_loss", 7.15e-4, True),
    ("step.index_loss", "index_loss", 0.37, False),      # float8 operands
    ("grad.wk_idx", "grad", 9.0e-3, True),
    ("grad.wk_idx", "grad", 0.62, False),                # float8 operands
    ("flips", "flips", float("nan"), False),
])
def test_salm_verdict_holds_a_reading_to_its_limit(name, reads, err, ok):
    """``families/salm.py::verdict`` — what ``first_step_check`` and
    ``tools/salm_lower_precision.py`` both decide by — with the chip's two
    readings of each limit (PERF.md section 6, PR 49) on their sides."""
    sys.path.insert(0, REPO)
    from benchmark.families import salm

    field = salm.verdict({name: err}, salm.LIMITS)[name]
    assert field["limit"] == salm.LIMITS[reads]
    assert field["ok"] is ok and (field["max_err"] == err or err != err)
