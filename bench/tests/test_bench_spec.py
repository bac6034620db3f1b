"""``BENCHMARK.json`` against the shape the benchmark's contract sets, and
every file a cell is found by."""
from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_command():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert s["command"] == ["python3", "bench/run.py"] and s["paths"] == ["bench"]
    assert 1 <= s["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (s["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_entries_and_names():
    s = spec()
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"}}
    for part, want in keys.items():
        for e in s[part]:
            assert set(e) == want, e
            assert NAME.match(e["name"])
            assert 1 <= len(e["why"]) <= 200
    for e in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
    for e in s["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for e in s["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    names = [e["name"] for p in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in s[p]]
    assert len(names) == len(set(names))


def test_every_cell_finds_its_files_and_reports_enough():
    s = spec()
    e2e = {e["name"]: e for e in s["end_to_end"]}
    cfgs = {c["name"]: c for c in s["configs"]}
    used = set()
    for w in s["workloads"]:
        c = cfgs[w["config"]]
        used.add(c["name"])
        for rel in (c["file"], f"bench/traffic/{w['traffic']}.json",
                    f"bench/limits/{w['name']}.json"):
            assert os.path.exists(os.path.join(REPO, rel)), rel
        mine = [n for n, e in e2e.items()
                if w["name"] in e.get("workloads", [w["name"]])]
        assert "setup_s" in mine and len(mine) >= 2
        layer = [m for m in s["per_layer"] if m["moves"] in mine
                 and w["name"] in m.get("workloads", [w["name"]])]
        assert layer, w["name"]
    assert used == set(cfgs)
    for m in s["per_layer"]:
        assert os.path.exists(os.path.join(REPO, "bench", "metrics",
                                           m["name"] + ".py")), m["name"]
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            owner = e2e[m["moves"]].get("workloads")
            assert owner is None or w in owner
    four = sum(w["chips"] == 4 for w in s["workloads"])
    assert four <= max(1, len(s["workloads"]) // 2)
