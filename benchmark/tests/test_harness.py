"""The harness end to end at toy size, and the pieces it owns."""

import json
import os
import subprocess
import sys
from dataclasses import dataclass

import numpy as np
import pytest

from benchmark.families.w2v import ChunkBatcher, as_cbow
from benchmark.lib import loop, spec

RUN = [sys.executable, os.path.join(spec.BENCH_DIR, "run.py")]


def run(*args, timeout=240, devices=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if devices:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(RUN + list(args), capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=spec.ROOT)


def test_without_a_tpu_there_is_no_result_line():
    p = run("--workload", "cbow2m-demo", "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_cell_is_named_before_jax_is_touched():
    p = run("--workload", "nope", "--rehearse-cpu")
    assert p.returncode != 0 and "no workload 'nope'" in p.stderr
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("cell, trace, devices", [
    ("cbow2m-demo", "0", None), ("gnews3m-x4-b64k", "1", None),
    ("gnews3m-x4-b64k", "0", 8)])      # the caller's device count wins
def test_cpu_rehearsal_end_to_end(cell, trace, devices):
    """The whole path at toy size: counts and correctness, ``platform:
    cpu``, and no time, rate or device metric in the result line."""
    p = run("--workload", cell, "--seed", "5", "--seconds", "1", "--trace",
            trace, "--rehearse-cpu", devices=devices)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert "platform: cpu" in lines[0]
    result = json.loads(lines[-1])
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == (devices or
                                         (4 if "x4" in cell else 1))
    assert set(result["metrics"]) <= {"train_loss_fixed"}
    assert "first step vs plain reference: ok" in p.stdout
    assert "programs lowered inside: 0" in p.stdout


@dataclass
class FakeBatch:
    centers: np.ndarray
    contexts: np.ndarray
    ctx_mask: np.ndarray
    n_words: int


class FakeInner:
    """An epoch of three full batches and a partial tail."""
    vocab = "the-vocab"

    def __init__(self):
        self.epochs = 0

    def epoch(self, batch_size):
        self.epochs += 1
        for i in range(4):
            n = batch_size if i < 3 else batch_size // 2
            yield FakeBatch(np.full(batch_size, 10 * self.epochs + i),
                            np.zeros((batch_size, 2), np.int32),
                            np.ones((batch_size, 2), bool), n)


class NoSpan:
    def __init__(self, _name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_chunk_batcher_cycles_full_batches_only():
    inner = FakeInner()
    b = ChunkBatcher(inner, NoSpan)
    assert b.vocab == "the-vocab"
    first = b.peek("epoch", 8)
    assert b.peek("epoch", 8) is first            # peeking consumes nothing
    b.steps = 2
    got = [int(x.centers[0]) for x in b.epoch(8)]
    assert got == [10, 11] and b.last.centers[0] == 11
    b.steps = 3                                   # crosses into epoch two,
    got = [int(x.centers[0]) for x in b.epoch(8)]  # skipping the tail batch
    assert got == [12, 20, 21] and inner.epochs == 2
    b.close()


def test_chunk_batcher_refuses_a_stream_without_a_full_batch():
    class Short(FakeInner):
        def epoch(self, batch_size):
            yield FakeBatch(np.zeros(batch_size), None, None, 1)

    with pytest.raises(RuntimeError, match="no full batch of 8"):
        ChunkBatcher(Short(), NoSpan).peek("epoch", 8)


def test_stencil_batches_expand_as_their_docstring_says():
    @dataclass
    class Stencil:
        tokens: np.ndarray
        sent_id: np.ndarray
        center_pos: np.ndarray
        half: np.ndarray

    s = Stencil(np.array([5, 6, 7, 8, 9]), np.array([0, 0, 0, 1, 1]),
                np.array([1, 3]), np.array([2, 1]))
    centers, contexts, mask = as_cbow(s, 2)
    assert centers.tolist() == [6, 8]
    # center 6 (pos 1, half 2): 5 and 7; pos 3 is the next sentence
    assert contexts[0, :2].tolist() == [5, 7] and mask[0].tolist() \
        == [True, True, False, False]
    # center 8 (pos 3, half 1): 7 is the previous sentence, 9 stays
    assert contexts[1, :1].tolist() == [9] and mask[1].sum() == 1


def test_a_failing_chunk_fails_all_its_steps_and_ends_the_window():
    class Family:
        calls = 0

        def run_chunk(self, steps):
            self.calls += 1
            if self.calls == 3:
                raise ValueError("boom")
            return steps * 10, 1.5 if self.calls == 1 else float("inf")

    class Counter:
        def mark(self):
            return {"lowered": 0}

    fam = Family()
    win = loop.measure(fam, 4, Counter(), lambda w: w.seconds >= 60.0)
    assert [c.failed for c in win.chunks] == [False, True, True]
    assert win.attempted == 12 and win.failed == 8 and fam.calls == 3
