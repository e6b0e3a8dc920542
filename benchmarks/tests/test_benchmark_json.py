"""A lint of BENCHMARK.json against the contract and against the files
the harness finds by name."""

import re

from lib import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_names_units_and_keys():
    bm = spec.load_benchmark()
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= bm["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bm[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bm["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)


def test_every_config_has_a_cell_and_every_cell_its_files():
    bm = spec.load_benchmark()
    used = {w["config"] for w in bm["workloads"]}
    assert used == {c["name"] for c in bm["configs"]}
    for c in bm["configs"]:
        cfg = spec.load_json(spec.REPO / c["file"])
        assert c["file"].startswith("benchmarks/configs/")
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"], bm)
        kind = cell["traffic"]["kind"]
        assert (spec.BENCH / "kinds" / f"{kind}.py").is_file()
    # what one pair alone has comes from the cell's own file, over the mix's
    mixes = {w["name"]: w["traffic"] for w in bm["workloads"]}
    for own in (spec.BENCH / "cells").glob("*.json"):
        mix = spec.load_json(spec.BENCH / "traffic"
                             / f"{mixes[own.stem]}.json")
        params = spec.load_json(own)["traffic"]
        assert not set(params) & set(mix), own
        assert params.items() <= spec.load_cell(own.stem, bm)["traffic"].items()


def test_layer_metrics_cells_report_what_they_move():
    bm = spec.load_benchmark()
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    cells = {w["name"] for w in bm["workloads"]}
    layers = {}
    for m in bm["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", cells), (m["name"], cell)
        f = spec.load_json(spec.BENCH / "layers" / f"{m['name']}.json")
        assert (f["layer"], f["unit"], f["moves"]) == (
            m["layer"], m["unit"], m["moves"])
        assert (spec.BENCH / "layers" / "readers"
                / f"{f['reader']}.py").is_file()
        layers.setdefault(m["layer"], []).append(m["name"])
    for cell in cells:
        c = spec.load_cell(cell, bm)
        assert len(spec.metrics_of(c, "end_to_end")) >= 2
        assert len(spec.metrics_of(c, "per_layer")) >= 1
