"""Asynchronous input pipeline tests (io/pipeline.py + the consumer
loops): PrefetchIterator semantics — order, end-of-stream with a full
queue, error propagation, close/unblock — the dispatch-depth
resolution, loss-accumulator retention, the host-stall meter split, and
the determinism contract: ``[worker] pipeline: K`` is bit-identical to
the synchronous loop on every backend and rendering, epoch tails
included.  Chaos: a crash mid-pipeline resumes from the consumed-step
checkpoint, and a producer-side batcher failure stays recoverable.
"""

import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from swiftmpi_tpu.data.text import CBOWBatcher, synthetic_corpus  # noqa: E402
from swiftmpi_tpu.io.pipeline import (PipelineError,  # noqa: E402
                                      PrefetchIterator,
                                      device_put_transfer)
from swiftmpi_tpu.io.resilience import train_with_resume  # noqa: E402
from swiftmpi_tpu.models.glove import GloVe  # noqa: E402
from swiftmpi_tpu.models.trainer import Trainer  # noqa: E402
from swiftmpi_tpu.models import transformer as tfm  # noqa: E402
from swiftmpi_tpu.models.word2vec import Word2Vec  # noqa: E402
from swiftmpi_tpu.testing import faults  # noqa: E402
from swiftmpi_tpu.testing.faults import FaultPlan, InjectedFault  # noqa: E402
from swiftmpi_tpu.utils import ConfigParser  # noqa: E402
from swiftmpi_tpu.utils.pipeline import (AUTO_BOUND,  # noqa: E402
                                         resolve_dispatch_bound)
from swiftmpi_tpu.utils.timers import Throughput  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_fault_bus():
    """No fault plan may leak between tests (the bus is process-global)."""
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# PrefetchIterator unit semantics
# ---------------------------------------------------------------------------

class TestPrefetchIterator:
    def test_order_preserved(self):
        assert list(PrefetchIterator(range(100), depth=4)) == list(range(100))

    def test_end_of_stream_with_full_queue_drops_nothing(self):
        """Regression: the end-of-stream sentinel must never displace a
        still-unconsumed item.  Fill the queue, let the producer exhaust
        its source and reach the sentinel put while the queue is still
        full, then drain — every item must arrive."""
        pipe = PrefetchIterator([0, 1, 2], depth=3)
        deadline = time.monotonic() + 5.0
        while pipe.stats()["produced"] < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.3)   # producer is now blocked putting the sentinel
        assert list(pipe) == [0, 1, 2]

    def test_transfer_applied_on_producer_in_order(self):
        pipe = PrefetchIterator(range(10), depth=2,
                                transfer=lambda x: x * 2)
        assert list(pipe) == [2 * i for i in range(10)]
        assert pipe.stats()["transfer_s"] >= 0.0

    def test_producer_error_after_queued_items(self):
        """Queued items drain first, THEN the producer's exception
        re-raises as PipelineError with the original chained."""
        def src():
            yield 1
            yield 2
            raise RuntimeError("boom")

        pipe = PrefetchIterator(src(), depth=4)
        got = [next(pipe), next(pipe)]
        with pytest.raises(PipelineError) as ei:
            next(pipe)
        assert got == [1, 2]
        assert isinstance(ei.value.__cause__, RuntimeError)
        assert "boom" in str(ei.value.__cause__)

    def test_close_unblocks_and_joins_producer(self):
        def infinite():
            i = 0
            while True:
                yield i
                i += 1

        pipe = PrefetchIterator(infinite(), depth=1)
        assert next(pipe) == 0
        pipe.close()
        assert not pipe._thread.is_alive()
        with pytest.raises(StopIteration):
            next(pipe)

    def test_close_is_idempotent(self):
        pipe = PrefetchIterator([1], depth=1)
        assert list(pipe) == [1]      # exhaustion closes
        pipe.close()
        pipe.close()

    def test_context_manager_closes(self):
        with PrefetchIterator(range(100), depth=2) as pipe:
            assert next(pipe) == 0
        assert not pipe._thread.is_alive()

    def test_depth_validation(self):
        with pytest.raises(ValueError):
            PrefetchIterator([1], depth=0)

    def test_stats_counts(self):
        pipe = PrefetchIterator(range(7), depth=2)
        out = list(pipe)
        s = pipe.stats()
        assert out == list(range(7))
        assert s["produced"] == s["consumed"] == 7
        assert 1 <= s["peak_queue_depth"] <= 2
        assert s["stall_s"] >= 0.0
        assert s["depth"] == 2


def test_device_put_transfer_places_array_leaves(devices8):
    mesh = Mesh(np.array(devices8), ("shard",))
    sharding = NamedSharding(mesh, P())
    put = device_put_transfer(sharding)
    item = ("group",
            (np.arange(6, dtype=np.int32).reshape(2, 3), jnp.ones(4)),
            [3, 5])
    kind, fields, n_words = put(item)
    assert kind == "group"            # non-array leaves pass through
    assert n_words == [3, 5]
    for f in fields:
        assert isinstance(f, jax.Array)
        assert f.sharding == sharding
    np.testing.assert_array_equal(np.asarray(fields[0]),
                                  np.arange(6).reshape(2, 3))


# ---------------------------------------------------------------------------
# Dispatch-depth resolution + loss-accumulator retention
# ---------------------------------------------------------------------------

def test_resolve_dispatch_bound():
    # synchronous loop: "auto" defers to the platform default
    assert resolve_dispatch_bound("auto", pipelined=False) == "auto"
    assert resolve_dispatch_bound(None, pipelined=False) == "auto"
    # pipelined: prefetch removed the input stall's accidental
    # backpressure, so "auto" becomes a concrete bound on EVERY backend
    assert resolve_dispatch_bound("auto", pipelined=True) == AUTO_BOUND
    assert resolve_dispatch_bound(None, pipelined=True) == AUTO_BOUND
    # explicit values win either way; 0 = unbounded
    assert resolve_dispatch_bound(4, pipelined=True) == 4
    assert resolve_dispatch_bound("4", pipelined=False) == 4
    assert resolve_dispatch_bound(0, pipelined=True) is None


@pytest.mark.parametrize("programs, held", [(1, 16), (2, 8), (3, 5)])
def test_dispatch_window_bounds_programs(programs, held, monkeypatch):
    """The window's bound is in programs: a loop whose step is
    ``programs`` of them keeps that many fewer steps in flight (XLA:CPU
    blocks the dispatching thread at 32 computations a device — the
    async pair's 16 steps)."""
    from swiftmpi_tpu.utils import pipeline
    from swiftmpi_tpu.utils.pipeline import DispatchWindow

    waited = []
    monkeypatch.setattr(pipeline.jax, "block_until_ready", waited.append)
    window = DispatchWindow(AUTO_BOUND, programs=programs)
    for step in range(40):
        window.push(step)
        assert len(window._window) <= held
    # oldest first, one a push once full
    assert waited == list(range(40 - held))
    unbounded = DispatchWindow(None, programs=programs)
    unbounded.push(0)
    assert unbounded._window == []


def test_throughput_stall_split():
    m = Throughput()
    m.record(100, steps=2)
    m.record(50)                        # steps defaults to 1
    m.add_stall(0.05)
    with m.stalling():
        time.sleep(0.02)
    assert m.host_stall_ms() >= 60.0
    assert m.stall_ms_per_step() == pytest.approx(m.host_stall_ms() / 3)
    assert m.rate() > 0.0
    s = m.stats()
    assert set(s) == {"items", "steps", "rate", "host_stall_ms",
                      "stall_ms_per_step"}
    assert s["items"] == 150.0 and s["steps"] == 3.0
    m.reset()
    assert m.host_stall_ms() == 0.0 and m.stall_ms_per_step() == 0.0


# ---------------------------------------------------------------------------
# Determinism: pipelined batch streams and training are bit-identical
# ---------------------------------------------------------------------------

def _corpus(n_sent=40, vocab=50, length=12, seed=6):
    return synthetic_corpus(n_sent, vocab_size=vocab, length=length,
                            seed=seed)


def _w2v(transfer, stencil, pipeline, inner=2):
    cfg = ConfigParser().update({
        "cluster": {"server_num": 2, "transfer": transfer},
        "word2vec": {"len_vec": 16, "window": 2, "negative": 5,
                     "sample": -1, "learning_rate": 0.05,
                     "min_sentence_length": 2, "stencil": stencil},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 512, "inner_steps": inner,
                   "pipeline": pipeline},
    })
    return Word2Vec(config=cfg)


def test_prefetch_batch_stream_identical(devices8):
    corp = _corpus()
    m = _w2v("xla", 0, 0)
    m.build(corp)
    plain = list(CBOWBatcher(corp, m.vocab, m.window, m.sample,
                             seed=5).epoch(64))
    piped = list(CBOWBatcher(corp, m.vocab, m.window, m.sample,
                             seed=5).epoch_prefetch(64, depth=3))
    assert len(plain) == len(piped) > 1
    for a, b in zip(plain, piped):
        assert a.n_words == b.n_words
        np.testing.assert_array_equal(a.centers, b.centers)
        np.testing.assert_array_equal(a.contexts, b.contexts)
        np.testing.assert_array_equal(a.ctx_mask, b.ctx_mask)


def test_prefetch_stencil_stream_identical(devices8):
    """The stencil wire format through the prefetch front-end: spans,
    sentence ids, positions and halves all match the inline epoch."""
    corp = _corpus()
    m = _w2v("xla", 1, 0)
    m.build(corp)
    plain = list(CBOWBatcher(corp, m.vocab, m.window, m.sample,
                             seed=5).epoch_stencil(32))
    piped = list(CBOWBatcher(corp, m.vocab, m.window, m.sample,
                             seed=5).epoch_stencil_prefetch(32, depth=2))
    assert len(plain) == len(piped) > 1
    for a, b in zip(plain, piped):
        assert a.n_words == b.n_words
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(a.sent_id, b.sent_id)
        np.testing.assert_array_equal(a.center_pos, b.center_pos)
        np.testing.assert_array_equal(a.half, b.half)


def _train_final(transfer, stencil, pipeline, corp, niters=2,
                 batch_size=64):
    m = _w2v(transfer, stencil, pipeline)
    m.build(corp)
    losses = m.train(corp, niters=niters, batch_size=batch_size)
    params = {k: np.asarray(v) for k, v in m.table.state.items()}
    return losses, params, m


@pytest.mark.parametrize("transfer,stencil",
                         [("xla", 0), ("xla", 1), ("tpu", 0),
                          ("hybrid", 0), ("hybrid", 1)])
@pytest.mark.slow
def test_pipeline_bit_identical_to_off(transfer, stencil, devices8):
    """The acceptance contract: same seed + corpus, ``pipeline: 3`` vs
    ``pipeline: 0`` — identical per-iteration losses AND bit-identical
    final parameters, per backend and rendering (the stencil rendering
    only exists on xla/hybrid — its span family needs push_span).  The
    corpus tail does not divide the fused group length, so the
    partial-group path is exercised too."""
    corp = _corpus()
    l_off, p_off, m_off = _train_final(transfer, stencil, 0, corp)
    l_on, p_on, m_on = _train_final(transfer, stencil, 3, corp)
    assert l_off == l_on
    assert set(p_off) == set(p_on)
    for k in p_off:
        np.testing.assert_array_equal(p_off[k], p_on[k])
    # and the pipeline actually ran: producer counters are live
    assert m_off.train_metrics["pipeline_depth"] == 0
    assert m_on.train_metrics["pipeline_depth"] == 3
    pipe = m_on.train_metrics["pipeline"]
    assert pipe["produced"] == pipe["consumed"] > 0
    assert pipe["peak_queue_depth"] >= 1
    for m in (m_off, m_on):
        tm = m.train_metrics
        assert tm["host_stall_ms"] >= 0.0
        assert tm["words_per_sec"] >= 0.0
        assert tm["stall_ms_per_step"] >= 0.0


def test_pipeline_epoch_tail_partial_group(devices8):
    """Explicitly pin the tail shape: with batch_size chosen so the
    epoch's batch count is NOT a multiple of inner_steps, the last item
    is a partial group — and parity still holds bit-tight."""
    corp = _corpus(n_sent=30, vocab=40, length=10, seed=9)
    m = _w2v("xla", 0, 0)
    m.build(corp)
    n_batches = sum(1 for _ in CBOWBatcher(
        corp, m.vocab, m.window, m.sample, seed=2008).epoch(64))
    assert n_batches % m.inner_steps != 0, \
        "shape drifted: tail no longer partial; retune the corpus"
    l_off, p_off, _ = _train_final("xla", 0, 0, corp)
    l_on, p_on, _ = _train_final("xla", 0, 2, corp)
    assert l_off == l_on
    for k in p_off:
        np.testing.assert_array_equal(p_off[k], p_on[k])


def test_glove_pipeline_parity(devices8):
    corp = _corpus(n_sent=30, vocab=40, length=12, seed=3)

    def run(pipeline):
        cfg = ConfigParser().update({
            "cluster": {"server_num": 2, "transfer": "xla"},
            "glove": {"len_vec": 8, "window": 4, "learning_rate": 0.05,
                      "minibatch": 32},
            "worker": {"inner_steps": 2, "pipeline": pipeline},
            "server": {"frag_num": 10},
        })
        m = GloVe(config=cfg)
        m.build(corp)
        losses = m.train(niters=2)
        return losses, {k: np.asarray(v) for k, v in m.table.state.items()}, m

    l_off, p_off, _ = run(0)
    l_on, p_on, m_on = run(3)
    assert l_off == l_on
    for k in p_off:
        np.testing.assert_array_equal(p_off[k], p_on[k])
    assert m_on.train_metrics["pipeline_depth"] == 3
    assert m_on.train_metrics["stall_ms_per_step"] >= 0.0


TFM_CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                n_heads=4, d_ff=64)


def _tfm_batches(n=6, batch=8, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TFM_CFG.vocab_size,
                         size=(batch, seq)).astype(np.int32)
            for _ in range(n)]


@pytest.mark.slow
def test_trainer_run_pipeline_parity(devices8):
    mesh = Mesh(np.array(devices8).reshape(4, 2), ("data", "model"))

    def run(pipeline):
        tr = Trainer(TFM_CFG, mesh=mesh, learning_rate=1e-2,
                     warmup_steps=2, decay_steps=100)
        state = tr.init_state(jax.random.key(0))
        state, losses = tr.run(state, _tfm_batches(), pipeline=pipeline)
        return tr, state, [float(x) for x in losses]

    tr0, s0, l0 = run(0)
    tr1, s1, l1 = run(2)
    assert l0 == l1
    np.testing.assert_array_equal(
        np.asarray(s0.params["blocks"]["wq"]),
        np.asarray(s1.params["blocks"]["wq"]))
    # consumed-step accounting identical; producer stats only on the
    # pipelined run, whose pre-transferred tokens skip the reshard stall
    assert tr0._host_steps == tr1._host_steps == 6
    assert tr0.pipeline_stats == {}
    assert tr1.pipeline_stats["produced"] == 6
    assert tr1.pipeline_stats["consumed"] == 6


def test_trainer_faults_count_consumed_steps(devices8):
    """``faults.step_event`` fires per CONSUMED step: with the pipeline
    on, a crash-at-step-3 plan trips after exactly 3 consumed steps even
    though the producer has rendered/transferred well past it."""
    mesh = Mesh(np.array(devices8).reshape(4, 2), ("data", "model"))
    tr = Trainer(TFM_CFG, mesh=mesh, learning_rate=1e-2, warmup_steps=2,
                 decay_steps=100)
    state = tr.init_state(jax.random.key(0))
    seen = []

    def observer(ev, step):
        seen.append((ev, step))

    faults.add_observer(observer)
    try:
        faults.install(FaultPlan().crash_at_step(3))
        with pytest.raises(InjectedFault):
            tr.run(state, _tfm_batches(n=10), pipeline=4)
    finally:
        faults.remove_observer(observer)
    assert tr._host_steps == 3
    steps = [s for ev, s in seen if ev == "step"]
    assert steps == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# Chaos: crash/recovery composes with the pipeline
# ---------------------------------------------------------------------------

def _resume_model(pipeline):
    cfg = ConfigParser().update({
        "cluster": {"transfer": "xla"},
        "word2vec": {"len_vec": 8, "window": 2, "negative": 3,
                     "sample": -1, "learning_rate": 0.05},
        "server": {"initial_learning_rate": 0.3},
        "worker": {"minibatch": 128, "inner_steps": 2,
                   "pipeline": pipeline},
    })
    return Word2Vec(config=cfg)


def test_chaos_crash_mid_pipeline_resumes_from_consumed_step(tmp_path,
                                                             devices8):
    """A crash at consumed step 3 with the pipeline on: the producer's
    in-flight items are dropped on the floor, resume restarts from the
    iter-3 checkpoint, and the run lands where the uninterrupted
    pipelined run lands."""
    corp = _corpus()
    clean = _resume_model(pipeline=3)
    clean.build(corp)
    clean_losses = clean.train(corp, niters=6, batch_size=64)

    plan = FaultPlan().crash_at_step(3)
    m = _resume_model(pipeline=3)
    m.build(corp)
    losses = train_with_resume(
        m, corp, niters=6, checkpoint_path=str(tmp_path / "ck"),
        checkpoint_every=1, max_restarts=2, retain=3, fault_plan=plan,
        batch_size=64)
    # crash fired at the top of iteration 3 -> checkpoints at iters
    # 1..3 landed -> exactly iterations 3,4,5 rerun
    assert len(losses) == 3
    rel = abs(losses[-1] - clean_losses[-1]) / abs(clean_losses[-1])
    assert rel < 0.2, (losses[-1], clean_losses[-1])


def test_producer_side_batcher_failure_is_recoverable(tmp_path, devices8):
    """A flaky batcher now fails on the PRODUCER thread; the consumer
    sees PipelineError (a RuntimeError) and train_with_resume retries
    from the checkpoint exactly as in the synchronous loop."""
    corp = _corpus(n_sent=30, vocab=50, length=12, seed=6)
    m = _resume_model(pipeline=3)
    m.build(corp)

    class FlakyBatcher:
        def __init__(self, inner, fail_on_epoch):
            self.inner = inner
            self.fail_on_epoch = fail_on_epoch
            self.epoch_i = 0

        def epoch(self, batch_size):
            self.epoch_i += 1
            for i, b in enumerate(self.inner.epoch(batch_size)):
                if self.epoch_i == self.fail_on_epoch and i == 1:
                    raise RuntimeError("injected render failure")
                yield b

    flaky = FlakyBatcher(
        CBOWBatcher(corp, m.vocab, m.window, m.sample), fail_on_epoch=3)
    losses = train_with_resume(
        m, niters=5, checkpoint_path=str(tmp_path / "resume_ck"),
        checkpoint_every=1, max_restarts=2, batcher=flaky, batch_size=64)
    assert len(losses) == 3
    assert np.isfinite(losses).all()
