"""BENCHMARK.json against the driver's contract, and the start-up check."""

import copy
import hashlib
import json
import os
import re
import shutil

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


ADDED = "added-cell"
JOINED = ("lm.head_ms_per_step", "lm.optimizer_ms_per_step")


def _digests(root):
    out = {}
    for base, _dirs, fnames in os.walk(os.path.join(root, "benchmark")):
        for fn in fnames:
            path = os.path.join(base, fn)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)


def _add_a_cell(root):
    """A thirteenth cell the way a ``model_config`` PR brings one: new files
    under ``benchmark/`` (a configuration of an accepted family, a traffic
    mix, a band, one per-layer metric with a reader block no file has),
    entries appended to ``BENCHMARK.json``, and the cell's name appended to
    the ``workloads`` of two accepted listed entries.  No file that was
    there is opened for writing."""
    bench = spec.load_json(os.path.join(root, "BENCHMARK.json"))
    like = next(w for w in bench["workloads"]
                if w["name"] == "trinity-ep16-16k-t16k")
    like_cfg = next(c for c in bench["configs"] if c["name"] == like["config"])
    sub = os.path.join(root, "benchmark")
    config = spec.load_json(os.path.join(root, like_cfg["file"]))
    _write(os.path.join(sub, "configs", "added-config.json"),
           dict(config, name="added-config"))
    traffic = spec.load_json(os.path.join(sub, "traffic",
                                          like["traffic"] + ".json"))
    _write(os.path.join(sub, "traffic", "added-mix.json"),
           dict(traffic, name="added-mix"))
    _write(os.path.join(sub, "bands", ADDED + ".json"),
           {"train_loss_fixed": [8.0, 9.0], "measured": "a test's"})
    entry = {"name": "added.counter_per_step", "unit": "rows",
             "better": "lower", "source": "program_counter",
             "layer": "experts", "moves": "words_per_s"}
    _write(os.path.join(sub, "layer_metrics", entry["name"] + ".json"),
           dict(entry, what="a counter only the added cell's program has",
                reader={"kind": "train_metrics", "key": "added_counter"}))
    bench["configs"].append(dict(like_cfg, name="added-config",
                                 file="benchmark/configs/added-config.json"))
    bench["workloads"].append(dict(like, name=ADDED, config="added-config",
                                   traffic="added-mix"))
    bench["per_layer"].append(dict(entry, workloads=[ADDED]))
    for m in bench["per_layer"]:
        if m["name"] in JOINED:
            m["workloads"].append(ADDED)
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return entry["name"]


TREES = pytest.mark.parametrize("tree", ["committed", "a-cell-added"])


def _tree(which, monkeypatch, tmp_path):
    """``None`` for the benchmark as committed; for ``a-cell-added``, points
    ``spec`` at a temporary copy of it to which a cell has been added by
    new files and ``BENCHMARK.json`` entries alone, and returns what the
    addition has to have left as it was.  (No fixture: tier-1 collects
    these cases by importing the tests' names alone.)"""
    if which == "committed":
        return None
    root = str(tmp_path)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(spec.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {"digests": _digests(root), "per_layer": {
        w["name"]: [m["name"] for m, _f in spec.load_cell(w["name"]).per_layer]
        for w in spec.load_benchmark()["workloads"]}}
    before["added_metric"] = _add_a_cell(root)
    monkeypatch.setattr(spec, "ROOT", root)
    monkeypatch.setattr(spec, "BENCH_DIR", os.path.join(root, "benchmark"))
    return before


@TREES
def test_benchmark_resolves(tree, monkeypatch, tmp_path):
    """The committed benchmark resolves, and so does one a cell was added
    to: an addition opens no accepted file under ``benchmark/``; a cell
    joins a metric by its name in that entry's ``workloads``."""
    before = _tree(tree, monkeypatch, tmp_path)
    assert spec.check() == []
    if before is None:
        return
    bench = spec.load_benchmark()
    assert len(bench["workloads"]) == len(before["per_layer"]) + 1
    listless = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert {m["name"] for m, _f in spec.load_cell(ADDED).per_layer} == \
        listless | set(JOINED) | {before["added_metric"]}
    for name, had in before["per_layer"].items():
        assert [m["name"] for m, _f in spec.load_cell(name).per_layer] == had
    now = _digests(spec.ROOT)
    assert {k: now[k] for k in before["digests"]} == before["digests"]
    assert len(now) == len(before["digests"]) + 4   # config, mix, band, metric
    # the other route is shut, by name: a second entry of an accepted reader
    # block for the new cell alone is sent to the first entry's list
    twin = next(m for m in bench["per_layer"]
                if m["name"] == "lm.embed_ms_per_step")
    assert ADDED not in twin["workloads"]
    shutil.copy(spec.bench_path("layer_metrics", twin["name"] + ".json"),
                spec.bench_path("layer_metrics", "twin.metric.json"))
    bench["per_layer"].append(dict(twin, name="twin.metric",
                                   workloads=[ADDED]))
    _write(os.path.join(spec.ROOT, "BENCHMARK.json"), bench)
    assert [p for p in spec.check() if "per_layer 'twin.metric': shares its "
            "reader with 'lm.embed_ms_per_step'" in p
            and f"add {[ADDED]} to that entry's 'workloads'" in p]


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


@TREES
def test_one_entry_a_reader_and_lists_that_agree(tree, monkeypatch, tmp_path):
    """No two ``per_layer`` entries' files share a ``reader`` block, in the
    committed benchmark or after an addition, and an entry's ``workloads``
    is the one list of its cells: no file carries ``cells``.  The only cap
    on the count is the contract's (``test_contract_limits``)."""
    _tree(tree, monkeypatch, tmp_path)
    b = spec.load_benchmark()
    seen = {}
    for m in b["per_layer"]:
        f = spec.load_json(spec.bench_path("layer_metrics",
                                           m["name"] + ".json"))
        assert "cells" not in f, m["name"]
        assert f["name"] == m["name"]
        assert len(set(m.get("workloads", []))) == len(m.get("workloads", []))
        key = json.dumps(f["reader"], sort_keys=True)
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]
    assert len(seen) == len(b["per_layer"])


def _with(monkeypatch, change, files=None):
    """``spec.check()`` on a changed copy of ``BENCHMARK.json``; ``files``
    gives ``layer_metrics`` files by entry name, each a function of the
    committed file of the name it stands in for."""
    bench = copy.deepcopy(spec.load_benchmark())
    change(bench)
    monkeypatch.setattr(spec, "load_benchmark", lambda: bench)
    real = spec.load_json

    def load_json(path):
        name = os.path.basename(path)[:-len(".json")]
        if os.path.basename(os.path.dirname(path)) == "layer_metrics" \
                and name in (files or {}):
            stands_for, made = files[name]
            return made(real(spec.bench_path("layer_metrics",
                                             stands_for + ".json")))
        return real(path)

    monkeypatch.setattr(spec, "load_json", load_json)
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


def _listed(b, name="lm.head_ms_per_step"):
    return next(m for m in b["per_layer"] if m["name"] == name)


BENCH_CASES = [
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
    (lambda b: _listed(b)["workloads"].append(_listed(b)["workloads"][0]),
     "lists cell 'lfm2-ep4-8k-t32k' twice"),
]
# ... and a metric's file that disagrees with its entry
FILE_CASES = [
    (lambda b: None, "layer_metrics/lm.head_ms_per_step.json carries 'cells'; "
     "the list lives in BENCHMARK.json",
     {"lm.head_ms_per_step": ("lm.head_ms_per_step",
                              lambda f: dict(f, cells=[]))}),
    (lambda b: None, "unit is 'ms' in BENCHMARK.json and 's' in its file",
     {"lm.head_ms_per_step": ("lm.head_ms_per_step",
                              lambda f: dict(f, unit="s"))}),
]


@pytest.mark.parametrize("change, said, files", [
    (change, said, None) for change, said in BENCH_CASES] + FILE_CASES)
def test_check_fails_fast_by_name(monkeypatch, change, said, files):
    problems = _with(monkeypatch, change, files)
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


@pytest.mark.parametrize("cells", [
    ["lfm2-ep4-8k-t32k"],            # a cell the first entry reads
    ["cbow2m-demo"]])                # a cell it does not: no second route
def test_a_second_entry_of_one_reader_is_sent_to_the_first(monkeypatch,
                                                           cells):
    """One entry a reader, at start-up: an entry whose file repeats an
    accepted entry's ``reader`` block is refused whatever cells it lists,
    and told where they belong."""
    first = _listed(spec.load_benchmark())
    problems = _with(
        monkeypatch, lambda b: b["per_layer"].append(
            dict(first, name="twin.metric", workloads=cells)),
        {"twin.metric": (first["name"], lambda f: f)})
    assert any("per_layer 'twin.metric': shares its reader with "
               f"{first['name']!r}" in p
               and f"add {cells} to that entry's 'workloads'" in p
               for p in problems), problems
