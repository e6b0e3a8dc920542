"""Finds a cell's files by the names in BENCHMARK.json.

A cell is `<config>.<traffic>`: its sizes are `configs/<config>.json`, its
traffic parameters `traffic/<traffic>.json`, what only this pair has (the
rate that a sweep of this configuration under this mix found)
`cells/<config>.<traffic>.json`, the code that drives that kind of
traffic `kinds/<kind>.py`, each per-layer metric `layers/<metric>.json`
and each reader kind `layers/readers/<kind>.py`. Adding any of them is
adding a file and one `workloads` entry.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file of the benchmark by path (kinds and readers have
    hyphens in their names and are no package)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark() -> dict:
    return load_json(REPO / "BENCHMARK.json")


def load_cell(name: str, benchmark: dict | None = None) -> dict:
    """Everything one run of `name` needs, from the files it names."""
    benchmark = benchmark or load_benchmark()
    entry = next((w for w in benchmark["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")
    config_entry = next(c for c in benchmark["configs"]
                        if c["name"] == entry["config"])
    config = load_json(REPO / config_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    own = BENCH / "cells" / f"{name}.json"
    if own.is_file():  # the cell's own parameters, over the mix's
        traffic = {**traffic, **load_json(own)["traffic"]}
    return {"name": name, "entry": entry, "config": config,
            "config_name": entry["config"], "traffic": traffic,
            "chips": entry["chips"], "benchmark": benchmark}


def metrics_of(cell: dict, group: str) -> list[dict]:
    """The `end_to_end` or `per_layer` metrics this cell reports: those
    that list it under `workloads`, and those with no such key (which
    every cell reports; a per-layer one then only where the cell reports
    the end-to-end metric it moves)."""
    bm = cell["benchmark"]
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    if group == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def kind_module(kind: str):
    return load_module(BENCH / "kinds" / f"{kind}.py",
                       "bench_kind_" + kind.replace("-", "_"))


def read_layer_metric(name: str, evidence: dict):
    """One per-layer metric by its own file and reader; None where the
    reader found nothing to read (the metric is then left out)."""
    spec = load_json(BENCH / "layers" / f"{name}.json")
    kind = spec["reader"]
    reader = load_module(BENCH / "layers" / "readers" / f"{kind}.py",
                         "bench_reader_" + kind.replace("-", "_"))
    return reader.read(spec.get("args", {}), evidence)
