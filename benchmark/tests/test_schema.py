"""BENCHMARK.json against the contract's shape, and every file it names."""

import importlib
import json
import os
import re

import pytest

from benchmark import harness as hs

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return hs.load_json(hs.ROOT, "BENCHMARK.json")


def test_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(hs.ROOT, "BENCHMARK.json")) < 65536
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["paths"]) <= 16
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_units_and_lines(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in bench[group]]
        assert len(set(names)) == len(names)
        for x in bench[group]:
            assert NAME.match(x["name"]), x["name"]
            for key in ("why", "layer", "source"):
                if key in x and group != "end_to_end" or key == "why":
                    if key in x:
                        assert 1 <= len(x[key]) <= 200 and "\n" not in x[key]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e


def test_cells_and_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    pairs = set()
    for cell in bench["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] in (1, 4)
        assert NAME.match(cell["traffic"])
        assert (cell["config"], cell["traffic"]) not in pairs
        pairs.add((cell["config"], cell["traffic"]))
        used.add(cell["config"])
        files = hs.cell_files(bench, cell)
        importlib.import_module("benchmark.drivers." + files["mix"]["kind"])
        importlib.import_module(
            "benchmark.reference." + files["config"]["reference"])
    assert used == set(configs)
    four = sum(c["chips"] == 4 for c in bench["workloads"])
    assert four <= max(len(bench["workloads"]) // 4, 1)
    for c in bench["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert len(c["reduced"]) <= 16
        assert hs.load_json(hs.ROOT, c["file"])["reduced"] == c["reduced"]


def test_every_metric_has_its_reader_and_every_cell_reports(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        spec = hs.load_json(hs.BENCH_DIR, "metrics", m["name"] + ".json")
        assert spec["unit"] == m["unit"]
        assert hasattr(importlib.import_module(
            "benchmark.readers." + spec["reader"]), "read")
    cells = {c["name"] for c in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for cell in bench["workloads"]:
        e2e = hs.wanted_metrics(bench, cell, False)
        assert "setup_s" in e2e and len(e2e) >= 2
        assert hs.wanted_metrics(bench, cell, True)
        moved = {m["moves"] for m in bench["per_layer"]
                 if cell["name"] in m.get("workloads", cells)}
        assert moved <= set(e2e)


def test_peaks_have_sources():
    table = hs.load_json(hs.BENCH_DIR, "peaks.json")
    for kind, row in table.items():
        if not kind.startswith("_"):
            assert row["source"] and row["flops_bf16"] and \
                row["hbm_bytes_per_s"]


def test_limits_name_their_readings(bench):
    for cell in bench["workloads"]:
        limits = hs.cell_files(bench, cell)["limits"]
        assert limits["limits"] and "readings" in limits
        json.dumps(limits)
