"""BENCHMARK.json keeps to its format, and every name in it resolves to
the files the harness looks up."""
import json
import os
import re

import pytest

import benchtest_util as bt

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_./-]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(bt.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    assert spec["command"] == ["python3", "bench/run.py"]
    assert isinstance(spec["run_seconds"], int) \
        and 1 <= spec["run_seconds"] <= 51
    size = os.path.getsize(os.path.join(bt.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_entries_names_and_units(spec):
    for kind, keys in ENTRY_KEYS.items():
        names = [e["name"] for e in spec[kind]]
        assert len(names) == len(set(names)), kind
        for e in spec[kind]:
            extra = {"workloads"} if kind in ("end_to_end", "per_layer") \
                else set()
            assert keys <= set(e) <= keys | extra, (kind, e["name"])
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
                assert e["source"] in SOURCES
            for k in ("why", "layer") + (("source",) if kind == "configs"
                                          else ()):
                if k in e:
                    assert _line(e[k]), (e["name"], k)
    metric_names = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]}
    cells = {w["name"] for w in spec["workloads"]}
    assert len(metric_names) == len(spec["end_to_end"]) + len(
        spec["per_layer"])
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}


def test_every_name_resolves(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    used = set()
    for w in spec["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        used.add(w["config"])
        cell = bt.run.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        for kind, name in (("datasets", cell.config["dataset"]),
                           ("jobs", cell.traffic["job"]),
                           ("entries", cell.traffic["entry"])):
            assert os.path.exists(os.path.join(bt.BENCH, kind, name + ".py"))
        assert cell.traffic["limits"], w["name"]
    assert used == set(configs)
    for c in spec["configs"]:
        assert FILE.match(c["file"]) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(bt.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(cfg["reduced"]) == set(c["reduced"])
        assert "assumed" in cfg and "deployment" in cfg
        assert _line(cfg["source"])
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(bt.BENCH, "metrics",
                                           m["name"] + ".py"))
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(spec["workloads"]) // 2)


def test_bench_files_named_from_name_characters():
    for base, dirs, files in os.walk(bt.BENCH):
        dirs[:] = [d for d in dirs if d not in ("__pycache__",
                                                "traces")]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), bt.ROOT)
            if "__pycache__" not in rel:
                assert FILE.match(rel), rel
