"""``BENCHMARK.json`` against the letter of its contract, as far as a file
can be checked without the chip."""

import json
import os
import re

from benchmarks.common import HERE, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_sizes():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= bench["run_seconds"] <= 51
    cells = len(bench["workloads"])
    # a full check with all 24 cells has to fit into 43200 seconds
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert sum(c["chips"] == 4 for c in bench["workloads"]) <= max(1, cells // 4)


def test_names_units_and_references():
    bench = _bench()
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {c["name"]: c for c in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in cells.values())
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert os.path.exists(
            os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
        reader = os.path.join(HERE, "layer_metrics", m["name"])
        assert os.path.exists(reader + ".json") or os.path.exists(reader + ".py")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert all(cell in cells for cell in m.get("workloads", []))
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for cell in cells:
        mine = [m["name"] for m in bench["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in _bench()["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in folder:
                continue
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert ok.match(rel), rel


def test_config_files_state_source_reduced_and_assumed():
    for c in _bench()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert "assumed" in config and "family" in config
        assert os.path.exists(
            os.path.join(HERE, "families", config["family"] + ".py"))


def test_candidates_follow_the_same_schema():
    with open(os.path.join(HERE, "candidates.json")) as f:
        waiting = json.load(f)
    admitted = {c["name"] for c in _bench()["workloads"]}
    cells = {c["name"] for c in waiting["workloads"]}
    assert not cells & admitted
    for c in waiting["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in waiting["workloads"]:
        assert NAME.match(w["name"]) and 1 <= len(w["why"]) <= 200
        assert os.path.exists(
            os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    e2e = {m["name"] for m in waiting["end_to_end"]}
    for m in waiting["end_to_end"] + waiting["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert set(m["workloads"]) <= cells
    for m in waiting["per_layer"]:
        assert m["moves"] in e2e
        reader = os.path.join(HERE, "layer_metrics", m["name"])
        assert os.path.exists(reader + ".json") or os.path.exists(reader + ".py")
