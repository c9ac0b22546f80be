"""``BENCHMARK.json`` names only files that exist, and only names, units and
keys the benchmark's contract allows."""

import json
import re

import bench_cases  # noqa: F401  (puts the checkout on sys.path)
import pytest

from bench import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "bench/run.py"]
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (harness.ROOT / p).is_dir()
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = harness.find_cell(cell, BENCH)
    w = c.entry
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    assert (harness.BENCH / "queries" / f"{c.traffic['query']}.py").is_file()
    assert (harness.BENCH / "loops" / f"{c.traffic['loop']}.py").is_file()
    for m in c.per_layer:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
    moved = {m["moves"] for m in c.per_layer}
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2 and moved <= reported


def test_configs():
    for conf in BENCH["configs"]:
        assert set(conf) == {"name", "source", "file", "reduced", "why"}
        body = json.loads((harness.ROOT / conf["file"]).read_text())
        assert body["name"] == conf["name"] and body["source"] == conf["source"]
        assert sorted(body["reduced"]) == sorted(conf["reduced"])
        # a cut of scale only: never a column's width or type
        assert not {"dtype", "n_columns"} & set(conf["reduced"])
        assert all(NAME.match(k) for k in conf["reduced"]) and len(conf["reduced"]) <= 16
        assert 1 <= len(conf["source"]) <= 200
        assert any(w["config"] == conf["name"] for w in BENCH["workloads"])


def test_names_units_and_sources():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    cells = {w["name"] for w in BENCH["workloads"]}
    assert len(names) == len(set(names)) and len(cells) == len(BENCH["workloads"])
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) == len(cells)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert {"rows_per_s", "setup_s"} <= set(names)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 2)
