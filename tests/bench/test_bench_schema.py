"""BENCHMARK.json: allowed names and units, and every file a cell or a
metric needs is there."""
import json
import os
import re

import _bench_path

ROOT = _bench_path.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bm():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_units_and_keys():
    bm = _bm()
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    names = []
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        names.append(w["name"])
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    assert 1 <= bm["run_seconds"] <= 51


def test_every_file_a_cell_names_exists():
    bm = _bm()
    configs = {c["name"]: c for c in bm["configs"]}
    for c in bm["configs"]:
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(ROOT, "bench", "models",
                                           conf["model"] + ".py"))
    for w in bm["workloads"]:
        assert w["config"] in configs
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py")), m["name"]
    for p in bm["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert bm["command"][1].startswith(bm["paths"][0] + "/")


def test_every_cell_reports_what_its_per_layer_metrics_move():
    bm = _bm()
    cells = [w["name"] for w in bm["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bm["end_to_end"]}
    assert "setup_s" in e2e
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in cells and w in e2e[m["moves"]], (m["name"], w)
    for w in cells:
        assert sum(w in v for v in e2e.values()) >= 2
        assert any(w in m.get("workloads", cells) for m in bm["per_layer"])
