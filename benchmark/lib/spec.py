"""BENCHMARK.json and the data files it names: loading and the start-up check.

Everything that belongs to one configuration, one traffic mix, one family
or one per-layer metric is a file of its own under ``benchmark/``, found by
the name ``BENCHMARK.json`` gives it.  ``check()`` proves that every name
resolves, so a later PR's additions fail fast, by name, before a chip run.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

E2E_SOURCES = ("host_clock", "device_trace")
SOURCES = E2E_SOURCES + ("program_span", "program_counter")

# the driver's limits on BENCHMARK.json: a file outside any of them is
# refused before a single run, so a builder meets them here, by name
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
MAX_BYTES = 64 * 1024
COUNTS = {"paths": (1, 16), "command": (1, 32), "configs": (1, 24),
          "workloads": (2, 24), "end_to_end": (1, 16), "per_layer": (1, 128)}
MAX_RUN_SECONDS = 51
MAX_REDUCED = 16
MAX_TEXT = 200                 # a why, a layer, a source, a word of command
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
# a key 'reduced' may never name: a width.  The guard is PR 22's, wide on
# purpose (any 'head', any 'state': a head count changes a projection's
# width), with one exemption: 'num_hidden_layers' is a depth.
WIDTH = re.compile(r"(hidden(?!_layers)|intermediate|latent|state|proj|head|"
                   r"expan|len_vec|_dim$|_rank$|experts_per)")


class SpecError(Exception):
    """A name in BENCHMARK.json or a data file that does not resolve."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file: {os.path.relpath(path, ROOT)}")
    except json.JSONDecodeError as e:
        raise SpecError(f"{os.path.relpath(path, ROOT)}: {e}")


def bench_path(*parts: str) -> str:
    return os.path.join(BENCH_DIR, *parts)


def load_benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def metric_cells(metric: dict, bench: dict) -> list:
    """Cells a metric is reported in: its ``workloads`` list in
    ``BENCHMARK.json`` — the one place the list lives — or all."""
    return list(metric.get("workloads")
                or [w["name"] for w in bench["workloads"]])


@dataclass
class Cell:
    """One ``workloads`` entry with its files loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # BENCHMARK.json entries reported in this cell
    per_layer: list       # (BENCHMARK.json entry, layer_metrics file) pairs
    band: dict            # bands/<cell>.json, {} if absent

    @property
    def family(self) -> str:
        return self.config["family"]


def _overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _overlay(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def load_cell(name: str, rehearse: bool = False) -> Cell:
    """Resolve a cell by name.  ``rehearse`` lays the ``rehearse`` block
    of the configuration and of the traffic mix over their real sizes."""
    bench = load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                        + ", ".join(w["name"] for w in bench["workloads"]))
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"workload {name!r}: no config {entry['config']!r}")
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(bench_path("traffic", entry["traffic"] + ".json"))
    if rehearse:
        config = _overlay(config, config.get("rehearse", {}))
        traffic = _overlay(traffic, traffic.get("rehearse", {}))
    band_path = bench_path("bands", name + ".json")
    band = load_json(band_path) if os.path.exists(band_path) else {}
    e2e = [m for m in bench["end_to_end"] if name in metric_cells(m, bench)]
    layer = [(m, load_json(bench_path("layer_metrics", m["name"] + ".json")))
             for m in bench["per_layer"] if name in metric_cells(m, bench)]
    return Cell(name, int(entry["chips"]), config, traffic, e2e, layer, band)


def four_chip_quota(cells: int) -> int:
    """Cells that may ask for 4 chips: a quarter, rounded down, or one."""
    return max(1, cells // 4)


def _text(where: str, key: str, value) -> list:
    if isinstance(value, str) and 1 <= len(value) <= MAX_TEXT \
            and "\n" not in value and "\t" not in value:
        return []
    return [f"{where}: {key!r} must be 1 to {MAX_TEXT} characters on one "
            f"line, has {len(value) if isinstance(value, str) else value!r}"]


def check_limits(bench: dict) -> list:
    """The driver's limits on ``BENCHMARK.json`` itself, each by name."""
    bad = []
    if set(bench) != KEYS:
        bad.append(f"BENCHMARK.json has the keys {sorted(bench)}, the "
                   f"contract takes exactly {sorted(KEYS)}")
        return bad
    size = len(json.dumps(bench, indent=2, ensure_ascii=False).encode())
    if size > MAX_BYTES:
        bad.append(f"BENCHMARK.json is {size} bytes, over the {MAX_BYTES} "
                   "the contract takes")
    for key, (lo, hi) in COUNTS.items():
        if not lo <= len(bench[key]) <= hi:
            bad.append(f"{key} has {len(bench[key])} entries, the contract "
                       f"takes {lo} to {hi}"
                       + ("; fold entries that share a reader "
                          "(benchmark/README.md)" if key == "per_layer"
                          else ""))
    secs = bench["run_seconds"]
    if not isinstance(secs, int) or not 1 <= secs <= MAX_RUN_SECONDS:
        bad.append(f"run_seconds is {secs!r}, not a whole number from 1 to "
                   f"{MAX_RUN_SECONDS}")
    for p in bench["paths"]:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            bad.append(f"paths: {p!r} is no relative path of the repo")
    for word in bench["command"]:
        bad += _text("command", "word", word)
    names = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for x in bench[key]:
            where = f"{key} {x.get('name')!r}"
            names.append(x.get("name"))
            if not isinstance(x.get("name"), str) \
                    or not NAME.match(x["name"]):
                bad.append(f"{where}: a name is 1 to 64 letters, digits, "
                           "'_', '.' and '-', the first a letter or a digit")
            for k in ("why", "layer", "source"):
                if k in x and not (k == "source" and key != "configs"):
                    bad += _text(where, k, x[k])
            if "unit" in x and not UNIT.match(str(x["unit"])):
                bad.append(f"{where}: unit {x['unit']!r} is not 1 to 16 "
                           "letters, digits, '_', '/', '%', '.' and '-'")
            if key in ("end_to_end", "per_layer") \
                    and x.get("better") not in ("lower", "higher"):
                bad.append(f"{where}: better is {x.get('better')!r}, not "
                           "'lower' or 'higher'")
    for name in sorted({n for n in names if names.count(n) > 1}):
        bad.append(f"the name {name!r} is used twice")
    files = [c["file"] for c in bench["configs"]]
    for c in bench["configs"]:
        where = f"configs {c['name']!r}"
        if files.count(c["file"]) > 1 or not PATH.match(c["file"]) or not \
                any(c["file"].startswith(p + "/") for p in bench["paths"]):
            bad.append(f"{where}: file {c['file']!r} must be its own, "
                       "under 'paths'")
        if len(c["reduced"]) > MAX_REDUCED:
            bad.append(f"{where}: 'reduced' has {len(c['reduced'])} keys, "
                       f"over {MAX_REDUCED}")
        for k in c["reduced"]:
            if not NAME.match(k) or WIDTH.search(k):
                bad.append(f"{where}: 'reduced' names {k!r}: a width may "
                           "never be reduced")
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    for pair in sorted({p for p in pairs if pairs.count(p) > 1}):
        bad.append(f"config {pair[0]!r} under traffic {pair[1]!r} appears "
                   "twice")
    for w in bench["workloads"]:
        if not NAME.match(str(w["traffic"])):
            bad.append(f"workloads {w['name']!r}: traffic {w['traffic']!r} "
                       "is no name")
    for m in bench["end_to_end"]:
        if not 0.01 <= m.get("bound", 0) <= 0.1:
            bad.append(f"end_to_end {m['name']!r}: bound {m.get('bound')!r} "
                       "is not within 0.01 and 0.1")
    return bad


def check() -> list:
    """Every problem found, as text; empty when the benchmark is whole."""
    try:
        bench = load_benchmark()
    except SpecError as e:
        return [str(e)]
    bad = check_limits(bench)
    if set(bench) != KEYS:
        return bad
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    used = set()
    for w in bench["workloads"]:
        where = f"workload {w['name']!r}"
        if w["chips"] not in (1, 4):
            bad.append(f"{where}: chips must be 1 or 4, not {w['chips']}")
        cfg = configs.get(w["config"])
        if cfg is None:
            bad.append(f"{where}: config {w['config']!r} is not in configs")
            continue
        used.add(w["config"])
        try:
            conf = load_json(os.path.join(ROOT, cfg["file"]))
            load_json(bench_path("traffic", w["traffic"] + ".json"))
        except SpecError as e:
            bad.append(f"{where}: {e}")
            continue
        fam = conf.get("family")
        for sub in ("families", "reference", "costs"):
            if not os.path.exists(bench_path(sub, f"{fam}.py")):
                bad.append(f"{where}: family {fam!r} has no "
                           f"benchmark/{sub}/{fam}.py")
        if int(conf.get("chips", w["chips"])) != w["chips"]:
            bad.append(f"{where}: asks for {w['chips']} chips, config "
                       f"{w['config']!r} stands for {conf['chips']}")
        if sorted(conf.get("reduced", [])) != sorted(cfg.get("reduced", [])):
            bad.append(f"config {w['config']!r}: 'reduced' differs between "
                       "BENCHMARK.json and its file")
    for name in set(configs) - used:
        bad.append(f"config {name!r} is used by no workload")
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    quota = four_chip_quota(len(bench["workloads"]))
    if four > quota:
        bad.append(f"{four} cells ask for 4 chips; a quarter of "
                   f"{len(bench['workloads'])} cells, rounded down, or one "
                   f"allows {quota}")
    for m in bench["end_to_end"]:
        if m["source"] not in E2E_SOURCES:
            bad.append(f"end_to_end {m['name']!r}: source {m['source']!r} "
                       f"is not one of {E2E_SOURCES}")
    if "setup_s" not in e2e:
        bad.append("end_to_end has no setup_s")
    declared, readers = set(), {}
    for m in bench["per_layer"]:
        where = f"per_layer {m['name']!r}"
        declared.add(m["name"])
        if m["source"] not in SOURCES:
            bad.append(f"{where}: source {m['source']!r} is not one of "
                       f"{SOURCES}")
        if m["moves"] not in e2e:
            bad.append(f"{where}: moves {m['moves']!r}, which is no "
                       "end_to_end metric")
        listed = m.get("workloads", [])
        for c in listed:
            if c not in cells:
                bad.append(f"{where}: cell {c!r} is no workload")
        for c in sorted({c for c in listed if listed.count(c) > 1}):
            bad.append(f"{where}: lists cell {c!r} twice")
        try:
            f = load_json(bench_path("layer_metrics", m["name"] + ".json"))
        except SpecError as e:
            bad.append(f"{where}: {e}")
            continue
        for key in ("unit", "layer", "moves", "source"):
            if f.get(key) != m[key]:
                bad.append(f"{where}: {key} is {m[key]!r} in BENCHMARK.json "
                           f"and {f.get(key)!r} in its file")
        if "cells" in f:
            bad.append(f"{where}: layer_metrics/{m['name']}.json carries "
                       "'cells'; the list lives in BENCHMARK.json, as the "
                       "entry's 'workloads'")
        kind = f.get("reader", {}).get("kind")
        if not kind or not os.path.exists(bench_path("readers",
                                                     f"{kind}.py")):
            bad.append(f"{where}: reader kind {kind!r} has no "
                       f"benchmark/readers/{kind}.py")
        # one entry a reader: a cell joins the entry that has the reader,
        # by its name in that entry's 'workloads'; no second entry
        block = json.dumps(f.get("reader"), sort_keys=True)
        if block in readers:
            bad.append(f"{where}: shares its reader with {readers[block]!r}; "
                       f"add {listed or 'its cells'} to that entry's "
                       "'workloads' in BENCHMARK.json instead")
        readers.setdefault(block, m["name"])
    for fn in sorted(os.listdir(bench_path("layer_metrics"))):
        if fn.endswith(".json") and fn[:-5] not in declared:
            bad.append(f"layer_metrics/{fn} is not declared under per_layer "
                       "in BENCHMARK.json")
    for w in bench["workloads"]:
        has_layer = any(w["name"] in metric_cells(m, bench)
                        for m in bench["per_layer"])
        n_e2e = sum(w["name"] in metric_cells(m, bench)
                    for m in bench["end_to_end"])
        if not has_layer or n_e2e < 2:
            bad.append(f"workload {w['name']!r} needs setup_s, one more "
                       "end_to_end metric and one per_layer metric")
    return bad
