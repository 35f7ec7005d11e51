"""BENCHMARK.json against the driver's contract, and the start-up check."""

import copy
import os
import re

import pytest

from benchmark.lib import spec

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head|expan|"
                   r"len_vec|_dim$|_rank$|experts_per)")


def test_benchmark_resolves():
    assert spec.check() == []


def test_contract_limits():
    b = spec.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 65536
    assert 1 <= len(b["paths"]) <= 16 and all(PATH.match(p)
                                              for p in b["paths"])
    assert len(b["command"]) <= 32
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert 1 <= len(b["configs"]) <= 24 and 2 <= len(b["workloads"]) <= 24
    assert 1 <= len(b["end_to_end"]) <= 16 and 1 <= len(b["per_layer"]) <= 128
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    files = [c["file"] for c in b["configs"]]
    assert len(files) == len(set(files))
    for c in b["configs"]:
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert not any(WIDTH.search(k) for k in c["reduced"]), c["reduced"]
    for x in b["configs"] + b["workloads"]:
        assert len(x["why"]) <= 200, (x["name"], len(x["why"]))
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["better"] in ("lower",
                                                              "higher")
    for root, _dirs, fnames in os.walk(spec.BENCH_DIR):
        for fn in fnames:
            rel = os.path.relpath(os.path.join(root, fn), spec.ROOT)
            if "__pycache__" not in rel:
                assert PATH.match(rel), rel


def _with(monkeypatch, change):
    bench = copy.deepcopy(spec.load_benchmark())
    change(bench)
    monkeypatch.setattr(spec, "load_benchmark", lambda: bench)
    return spec.check()


@pytest.mark.parametrize("change, said", [
    (lambda b: b["workloads"].append(
        {"name": "x4-two", "config": "w2v-cbow-gnews-3m-300",
         "traffic": "zipf-b16k", "chips": 4, "why": ""}),
     "2 cells ask for 4 chips"),
    (lambda b: b["workloads"][0].update(traffic="no-such-mix"),
     "missing file: benchmark/traffic/no-such-mix.json"),
    (lambda b: b["workloads"][0].update(config="no-such-config"),
     "config 'no-such-config' is not in configs"),
    (lambda b: b["per_layer"][0].update(moves="no_such_metric"),
     "moves 'no_such_metric'"),
    (lambda b: b["per_layer"][0].update(workloads=["no-such-cell"]),
     "cell 'no-such-cell' is no workload"),
    (lambda b: b["per_layer"].pop(0),
     "is not declared under per_layer"),
    (lambda b: b["per_layer"].append(
        {"name": "new.metric", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "step", "moves": "words_per_s"}),
     "missing file: benchmark/layer_metrics/new.metric.json"),
    (lambda b: b["end_to_end"][0].update(source="program_counter"),
     "source 'program_counter'"),
    (lambda b: b["workloads"][2].update(chips=1),
     "asks for 1 chips"),
])
def test_check_fails_fast_by_name(monkeypatch, change, said):
    problems = _with(monkeypatch, change)
    assert any(said in p for p in problems), problems


def test_unknown_cell_is_named():
    with pytest.raises(spec.SpecError, match="no workload 'nope'"):
        spec.load_cell("nope")


def test_rehearsal_overlays_toy_sizes_only_when_asked():
    real = spec.load_cell("cbow2m-b16k")
    toy = spec.load_cell("cbow2m-b16k", rehearse=True)
    assert real.config["vocab_size"] == 1_800_000
    assert real.config["word2vec"]["len_vec"] == 300
    assert toy.config["vocab_size"] < 100_000
    assert toy.config["word2vec"]["window"] == 5      # the rest is kept
