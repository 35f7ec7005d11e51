"""BENCHMARK.json against the driver's contract, and the start-up check."""

import copy
import json
import os
import re

import pytest

from benchmark.lib import spec

# The contract's patterns, written out here and NOT taken from ``spec``: the
# test is the second witness of ``spec.check_limits``, so a pattern loosened
# there fails here.  WIDTH is PR 22's with one exemption, ``num_hidden_layers``
# (a depth): any other key with ``hidden``, ``head`` or ``state`` in it stays
# a width, the head counts too.
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head|expan|"
                   r"len_vec|_dim$|_rank$|experts_per)")


def is_width(key: str) -> bool:
    return key != "num_hidden_layers" and bool(WIDTH.search(key))


def test_benchmark_resolves():
    assert spec.check() == []


def test_contract_limits():
    """The limits as the contract words them, with this file's own patterns,
    beside ``spec.check_limits`` (which a run applies at start-up): two
    copies on purpose, each the other's witness."""
    b = spec.load_benchmark()
    assert spec.check_limits(b) == []
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
        assert not any(is_width(k) for k in c["reduced"]), c["reduced"]
    for x in b["configs"] + b["workloads"]:
        assert len(x["why"]) <= 200, (x["name"], len(x["why"]))
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["better"] in ("lower",
                                                              "higher")
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "bound", "workloads"}, m
    for root, _dirs, fnames in os.walk(spec.BENCH_DIR):
        for fn in fnames:
            rel = os.path.relpath(os.path.join(root, fn), spec.ROOT)
            if "__pycache__" not in rel:
                assert PATH.match(rel), rel


@pytest.mark.parametrize("key, width", [
    ("num_hidden_layers", False), ("num_experts", False),
    ("n_routed_experts", False), ("vocab_size", False),
    ("hidden_size", True), ("intermediate_size", True),
    ("moe_intermediate_size", True), ("kv_lora_rank", True),
    ("qk_rope_head_dim", True), ("head_dim", True), ("ssm_state_size", True),
    ("num_experts_per_tok", True), ("len_vec", True), ("expansion", True),
    ("q_proj_size", True), ("num_attention_heads", True),
    ("num_key_value_heads", True), ("mamba_num_heads", True),
    ("indexer_num_heads", True), ("indexer_num_kv_heads", True),
    ("hidden_act", True), ("d_state", True)])
def test_a_depth_is_no_width(key, width):
    """``spec``'s guard and this file's agree, key by key: the one key PR 51
    exempts is the depth, and a head count is still refused."""
    assert bool(spec.WIDTH.search(key)) == width == is_width(key)


@pytest.mark.parametrize("name, ok", [
    ("lm.route_ms_per_step", True), ("9cells", True), ("n" * 64, True),
    ("n" * 65, False), ("_private", False), (".dot", False), ("a b", False),
    ("a/b", False), ("", False)])
def test_a_name_as_the_contract_had_it(name, ok):
    assert bool(spec.NAME.match(name)) == ok == bool(NAME.match(name))


def test_one_entry_a_reader_and_lists_that_agree():
    """No two ``per_layer`` entries whose files share a ``reader`` block
    list the same cell (since PR 51 none share one at all), and every
    file's ``cells`` equals its entry's ``workloads``."""
    b = spec.load_benchmark()
    all_cells = [w["name"] for w in b["workloads"]]
    seen = {}
    for m in b["per_layer"]:
        f = spec.load_json(spec.bench_path("layer_metrics",
                                           m["name"] + ".json"))
        assert f["cells"] == m.get("workloads", []), m["name"]
        assert f["name"] == m["name"]
        key = json.dumps(f["reader"], sort_keys=True)
        for c in m.get("workloads") or all_cells:
            assert (key, c) not in seen, (m["name"], seen[key, c], c)
            seen[key, c] = m["name"]
    assert len({k for k, _c in seen}) == len(b["per_layer"]) <= 80


def _with(monkeypatch, change):
    bench = copy.deepcopy(spec.load_benchmark())
    change(bench)
    monkeypatch.setattr(spec, "load_benchmark", lambda: bench)
    return spec.check()


def _four_chip_cells_over_quota(b):
    """Turn one-chip cells into four-chip ones until the quota is passed."""
    quota = spec.four_chip_quota(len(b["workloads"]))
    four = sum(w["chips"] == 4 for w in b["workloads"])
    for w in b["workloads"]:
        if four > quota:
            break
        if w["chips"] == 1:
            w["chips"], four = 4, four + 1


def _entry(name):
    return {"name": name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "step", "moves": "words_per_s"}


@pytest.mark.parametrize("change, said", [
    (_four_chip_cells_over_quota, "cells ask for 4 chips"),
    (lambda b: b["per_layer"].extend(
        _entry(f"filler.{i}") for i in range(129 - len(b["per_layer"]))),
     "per_layer has 129 entries, the contract takes 1 to 128; fold"),
    (lambda b: b["workloads"].extend(
        dict(b["workloads"][0], name=f"cell-{i}", traffic=f"mix-{i}")
        for i in range(25 - len(b["workloads"]))),
     "workloads has 25 entries, the contract takes 2 to 24"),
    (lambda b: b["per_layer"][0].update(name="n" * 65),
     f"per_layer '{'n' * 65}': a name is 1 to 64"),
    (lambda b: b["workloads"][0].update(why="y" * 201),
     "'why' must be 1 to 200 characters"),
    (lambda b: b["configs"][3]["reduced"].append("hidden_size"),
     "'reduced' names 'hidden_size': a width"),
    (lambda b: b["configs"][3]["reduced"].append("num_attention_heads"),
     "'reduced' names 'num_attention_heads': a width"),
    (lambda b: b["configs"][7]["reduced"].append("mamba_num_heads"),
     "'reduced' names 'mamba_num_heads': a width"),
    (lambda b: b["per_layer"][0].update(name="_led"),
     "per_layer '_led': a name is 1 to 64"),
    (lambda b: b["per_layer"][0].update(unit="ms a step"),
     "unit 'ms a step'"),
    (lambda b: b["end_to_end"][0].update(bound=0.5),
     "bound 0.5 is not within"),
    (lambda b: b.update(extra=1), "the contract takes exactly"),
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
    (lambda b: b["per_layer"].append(_entry("new.metric")),
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


def test_a_second_entry_of_one_reader_is_sent_to_the_first(monkeypatch,
                                                           tmp_path):
    """The rule of PR 51's fold at start-up: an entry whose file repeats
    another's ``reader`` block in a cell that one already reads is refused,
    and told where the cell belongs."""
    first = spec.load_benchmark()["per_layer"][0]
    real = spec.load_json

    def load_json(path):
        if os.path.basename(path) == "twin.metric.json":
            path = spec.bench_path("layer_metrics", first["name"] + ".json")
        return real(path)

    monkeypatch.setattr(spec, "load_json", load_json)
    problems = _with(monkeypatch, lambda b: b["per_layer"].append(
        dict(first, name="twin.metric")))
    assert any("per_layer 'twin.metric': reads cell" in p
               and f"{first['name']!r} already reads it" in p
               for p in problems), problems
