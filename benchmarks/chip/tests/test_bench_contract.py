"""BENCHMARK.json keeps to its schema, and every file the harness finds
by a name in it exists."""
import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.exists(os.path.join(ROOT, bench["command"][1]))


def test_names_and_units(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_cell_finds_its_files(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(BENCH, "limits",
                                           w["name"] + ".json"))
        mine = [m for m in bench["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert {"setup_s"} < {m["name"] for m in mine}
        layer = [m for m in bench["per_layer"]
                 if w["name"] in m.get("workloads", [w["name"]])]
        assert layer
        for m in layer:
            assert m["moves"] in {x["name"] for x in mine}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


def test_configs_are_used_and_found(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmarks/chip/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["source"].startswith("https://")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert all(k in cfg for k in c["reduced"])


def test_layers_are_named_alike(bench):
    for m in bench["per_layer"]:
        assert m["layer"] and "\n" not in m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
