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
                           "device", "compared"}
    assert list(result)[-1] == "compared"       # the numbers beside limits
    compared = result["compared"]
    for name, (value, limit) in compared.items():
        assert limit is not None and 0.0 <= value <= limit, (name, value,
                                                             limit)
    # one number at least behind each check a CPU run makes; no band there
    assert {n.split(".")[0] for n in compared} == {
        "first_step", "rows", "losses", "window", "table", "steps"}
    assert p.stderr.rstrip().splitlines()[-len(compared):] == [
        f"[bench] compared {name}: {value!r} limit {limit!r}"
        for name, (value, limit) in compared.items()]
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


def _sound_run():
    """The arguments of ``run.compared_numbers`` for a run with no fault."""
    from types import SimpleNamespace as NS

    cell = NS(family="w2v")
    first = {"ok": True, "loss": 0.7, "fields": {
        "h": {"max_err": 5e-6, "ok": True},
        "v": {"max_err": 2e-3, "limit": 1e-2, "ok": True}}}
    rows = {"ok": True, "unoccupied_unchanged": True,
            "live_rows_moved": "4/4", "finite": True}
    warm = [loop.Chunk(4, 40, 1.0, 0.69)]
    win = loop.Window([loop.Chunk(4, 40, 1.0, 0.68)], 1.0, {"lowered": 0})
    places = ({"why": [], "table_bytes": 100}, {"why": [], "table_bytes": 100})
    return dict(cell=cell, first=first, rows=rows, warm=warm, win=win,
                loss_fixed=0.5, band=[0.4, 0.6], places=places, peaks=[60, 60])


@pytest.mark.parametrize("fault, name, check", [
    (None, None, None),
    (lambda a: a["first"]["fields"]["h"].update(max_err=2e-4),
     "first_step.h", "first_step."),
    (lambda a: a["first"]["fields"]["v"].update(max_err=float("nan")),
     "first_step.v", "first_step."),
    (lambda a: a["first"].update(ok=False), "first_step.not_ok",
     "first_step."),
    (lambda a: a["rows"].update(live_rows_moved="3/4"),
     "rows.not_live_rows_moved", "rows."),
    (lambda a: a["rows"].update(unoccupied_unchanged=False),
     "rows.not_unoccupied_unchanged", "rows."),
    (lambda a: a["rows"].update(ok=False), "rows.not_ok", "rows."),
    (lambda a: a["warm"].append(loop.Chunk(4, 40, 1.0, float("inf"))),
     "losses.not_finite", "losses."),
    (lambda a: a.update(loss_fixed=0.61), "train_loss_fixed",
     "train_loss_fixed"),
    (lambda a: a["win"].compiles.update(lowered=1),
     "window.programs_lowered", "window."),
    (lambda a: a["places"][1]["why"].append("field 'h' lives on ['cpu']"),
     "table.fields_misplaced", "table."),
    (lambda a: a.update(peaks=[40, 40]), "table.bytes_over_peaks", "table."),
    (lambda a: a["win"].chunks.append(loop.Chunk(4, 40, 1.0, 0.6, True)),
     "steps.failed", "steps."),
])
def test_each_number_compared_decides_its_check(fault, name, check):
    """``compared`` is what ``checks`` is computed from: a sound run is
    within every limit, and each breach shows in its own number and fails
    the check of its prefix, no other."""
    from benchmark import run as harness

    args = _sound_run()
    if fault:
        fault(args)
    compared = harness.compared_numbers(**args)
    assert compared["first_step.h"][1] == 1e-4       # the reference's RTOL
    assert all(limit is not None for _v, limit in compared.values())
    prefixes = ("first_step.", "rows.", "losses.", "train_loss_fixed",
                "window.", "table.", "steps.")
    failed = [p for p in prefixes if not harness.within(compared, p)]
    assert failed == ([check] if fault else [])
    if fault:
        value, limit = compared[name]
        assert not harness.within({name: [value, limit]}, name)
