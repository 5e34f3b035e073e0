"""BENCHMARK.json against the character and consistency rules of the
benchmark's contract, and against the files the harness finds by name.
Run it before any chip call:  python -m pytest benchmarks/tests/test_manifest.py
"""
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def m():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024
    return json.loads(raw)


def line(text, most=200):
    return (isinstance(text, str) and 1 <= len(text) <= most
            and "\n" not in text and "\t" not in text)


def test_top_level(m):
    assert set(m) == TOP
    assert 1 <= len(m["command"]) <= 32
    for word in m["command"]:
        assert line(word) and not word.startswith("/") \
            and ".." not in word.split("/")
    assert 1 <= len(m["paths"]) <= 16
    for p in m["paths"]:
        assert PATH.match(p) and os.path.isdir(os.path.join(ROOT, p))
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    # a full check with the full 24 cells must fit the driver's limit
    s = m["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_and_units(m):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in m[group]]
        assert len(seen) == len(set(seen)), f"duplicate name in {group}"
        names += seen
    metric_names = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for n in names:
        assert NAME.match(n), n
    for w in m["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and line(w["why"])
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in m["per_layer"]:
        assert set(e) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        # the driver's words for PR 22: a layer is a NAME
        assert NAME.match(e["layer"]), e["layer"]
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    assert 1 <= len(m["end_to_end"]) <= 16
    assert 1 <= len(m["per_layer"]) <= 128
    assert 1 <= len(m["workloads"]) <= 24 and 1 <= len(m["configs"]) <= 24


def cells_of(metric, m):
    return set(metric.get("workloads", [w["name"] for w in m["workloads"]]))


def test_metrics_cover_cells(m):
    cells = {w["name"] for w in m["workloads"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for e in m["end_to_end"] + m["per_layer"]:
        assert cells_of(e, m) <= cells, e["name"]
    for e in m["per_layer"]:
        assert e["moves"] in e2e, e
        assert cells_of(e, m) <= cells_of(e2e[e["moves"]], m), (
            f"{e['name']} moves {e['moves']}, which not every one of "
            "its cells reports")
    for c in cells:
        assert any(c in cells_of(e, m) and e["name"] != "setup_s"
                   for e in m["end_to_end"]), c
        assert any(c in cells_of(e, m) for e in m["per_layer"]), c
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(cells) // 4)


def test_files_exist(m):
    used = set()
    files = set()
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line(c["source"]) and line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert PATH.match(c["file"]) and c["file"] not in files
        files.add(c["file"])
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert os.path.isfile(os.path.join(
            BENCH, "drivers", conf["driver"] + ".py"))
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        # the plain reference beside it
        assert conf["reference"]["file"] == \
            f"benchmarks/configs/{c['name']}_reference.py"
        assert os.path.isfile(os.path.join(ROOT, conf["reference"]["file"]))
        assert conf["reduced"] == c["reduced"]
    configs = {c["name"] for c in m["configs"]}
    for w in m["workloads"]:
        assert w["config"] in configs
        used.add(w["config"])
        path = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        with open(path) as f:
            assert json.load(f)["generator"] in (
                "closed_loop", "poisson", "fixed_batches")
    assert used == configs, "a configuration no cell uses"
    for e in m["per_layer"]:
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", e["name"] + ".py")), e["name"]
    for base, _, names in os.walk(BENCH):
        if "__pycache__" in base:
            continue
        for n in names:
            rel = os.path.relpath(os.path.join(base, n), ROOT)
            assert PATH.match(rel), rel
