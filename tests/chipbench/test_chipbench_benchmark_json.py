"""BENCHMARK.json keeps to the form the driver reads, and every cell,
configuration, traffic mix and per-layer metric it names has the files
the harness finds by that name."""
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CHIP = ROOT / "benchmarks" / "chip"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert ".." not in p and not p.startswith("/")
        assert (ROOT / p).is_dir()
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32
    assert (ROOT / cmd[1]).is_file()
    assert any(cmd[1].startswith(p + "/") for p in BENCH["paths"])


@pytest.mark.parametrize("group", sorted(ENTRY_KEYS))
def test_entries(group):
    entries = BENCH[group]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        extra = {"workloads"} if group in ("end_to_end", "per_layer") \
            else set()
        assert ENTRY_KEYS[group] <= set(e) <= ENTRY_KEYS[group] | extra
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e and group != "end_to_end" and group != "per_layer":
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")


def test_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {c["name"] for c in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved)
        assert (CHIP / "metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_reports_setup_another_metric_and_a_layer():
    e2e = BENCH["end_to_end"]
    for c in BENCH["workloads"]:
        mine = [m["name"] for m in e2e
                if c["name"] in m.get("workloads", [c["name"]])]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(c["name"] in m["workloads"] for m in BENCH["per_layer"])


def test_cells_configs_and_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    used = set()
    for c in BENCH["workloads"]:
        assert c["chips"] in (1, 4) and len(c["why"]) <= 200
        assert c["name"] == f"{c['config']}.{c['traffic']}"
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])
        used.add(c["config"])
        assert (CHIP / "traffic" / f"{c['traffic']}.json").is_file()
        cell = json.loads((CHIP / "cells" / f"{c['name']}.json").read_text())
        assert cell["limits"] and all(v > 0 for v in cell["limits"].values())
    assert used == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for c in configs.values():
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert set(c["reduced"]) == set(body.get("reduced", {}))
        for key in c["reduced"]:
            assert NAME.match(key) and key in body
            assert not re.search(r"(size|_dim|_rank|heads|per_tok)$", key)
