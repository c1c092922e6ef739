"""``BENCHMARK.json`` against the data files it names, and against the
letter of its contract that a slip of the pen would break (names,
units, lengths, the keys an entry may have). A later PR that adds a
cell, a configuration or a metric adds files and entries; this check
then says whether the two still agree.
"""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
B = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return _load(ROOT, "BENCHMARK.json")


def test_top_level(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert manifest["paths"] == ["benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_configs_match_their_files(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        f = _load(ROOT, c["file"])
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert (f["name"], f["source"], f["reduced"], f["why"]) == \
            (c["name"], c["source"], c["reduced"], c["why"])
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text
        assert f.get("reference"), "a configuration in the manifest " \
            "needs a plain reference"
        assert os.path.exists(os.path.join(
            B, "references", f["reference"] + ".py"))
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}


def test_cells_match_their_files(manifest):
    seen = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        f = _load(B, "workloads", w["name"] + ".json")
        for k in w:
            assert f[k] == w[k], (w["name"], k)
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert f["limits"], "a cell in the manifest compares something"
        kinds = {_load(B, "metrics", m + ".json")["kind"]
                 for m in f["metrics"]}
        assert kinds == {"end_to_end", "per_layer"}
        assert "setup_s" in f["metrics"]


def test_metrics_match_their_files(manifest):
    cells = {w["name"]: _load(B, "workloads", w["name"] + ".json")
             for w in manifest["workloads"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        per_layer = m in manifest["per_layer"]
        keys = {"name", "unit", "better", "source"} | (
            {"layer", "moves"} if per_layer else {"bound"})
        assert keys <= set(m) <= keys | {"workloads"}
        f = _load(B, "metrics", m["name"] + ".json")
        assert f["kind"] == ("per_layer" if per_layer else "end_to_end")
        for k in ("unit", "better", "source") + (
                ("layer", "moves") if per_layer else ()):
            assert f[k] == m[k], (m["name"], k)
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(B, "readers",
                                           f["reader"] + ".py"))
        reporting = [n for n, c in cells.items() if m["name"] in c["metrics"]]
        assert m.get("workloads", list(cells)) == reporting, m["name"]
        if per_layer:
            assert m["moves"] in e2e
            assert m["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
            moved = next(e for e in manifest["end_to_end"]
                         if e["name"] == m["moves"])
            assert set(reporting) <= set(moved.get("workloads", list(cells)))
        else:
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
