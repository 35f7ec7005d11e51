"""BENCHMARK.json and the data files it names: loading and the start-up check.

Everything that belongs to one configuration, one traffic mix, one family
or one per-layer metric is a file of its own under ``benchmark/``, found by
the name ``BENCHMARK.json`` gives it.  ``check()`` proves that every name
resolves, so a later PR's additions fail fast, by name, before a chip run.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

E2E_SOURCES = ("host_clock", "device_trace")
SOURCES = E2E_SOURCES + ("program_span", "program_counter")


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
    """Cells a metric is reported in: its ``workloads`` list, or all."""
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


def check() -> list:
    """Every problem found, as text; empty when the benchmark is whole."""
    bad = []
    try:
        bench = load_benchmark()
    except SpecError as e:
        return [str(e)]
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
    quota = max(1, len(bench["workloads"]) // 4)
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
    declared = set()
    for m in bench["per_layer"]:
        where = f"per_layer {m['name']!r}"
        declared.add(m["name"])
        if m["source"] not in SOURCES:
            bad.append(f"{where}: source {m['source']!r} is not one of "
                       f"{SOURCES}")
        if m["moves"] not in e2e:
            bad.append(f"{where}: moves {m['moves']!r}, which is no "
                       "end_to_end metric")
        for c in m.get("workloads", []):
            if c not in cells:
                bad.append(f"{where}: cell {c!r} is no workload")
        try:
            f = load_json(bench_path("layer_metrics", m["name"] + ".json"))
        except SpecError as e:
            bad.append(f"{where}: {e}")
            continue
        for key in ("unit", "layer", "moves", "source"):
            if f.get(key) != m[key]:
                bad.append(f"{where}: {key} is {m[key]!r} in BENCHMARK.json "
                           f"and {f.get(key)!r} in its file")
        if sorted(f.get("cells", [])) != sorted(m.get("workloads", [])):
            bad.append(f"{where}: cells differ between BENCHMARK.json "
                       "('workloads') and its file ('cells')")
        kind = f.get("reader", {}).get("kind")
        if not kind or not os.path.exists(bench_path("readers",
                                                     f"{kind}.py")):
            bad.append(f"{where}: reader kind {kind!r} has no "
                       f"benchmark/readers/{kind}.py")
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
