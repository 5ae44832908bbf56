"""Every cell of ``BENCHMARK.json`` resolves to its configuration, traffic
and metric files, and every name, unit and cross-reference keeps to the
benchmark's rules."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def _reports(cell: str, metric: dict) -> bool:
    return cell in metric.get("workloads", [cell])


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    for path in BENCH["paths"]:
        assert (ROOT / path).is_dir()
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    assert E2E["setup_s"]["bound"] <= 0.25
    assert all(0.0 < m["bound"] <= 0.25 for m in E2E.values())


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_resolves_to_its_files(cell):
    w = CELLS[cell]
    configs = {c["name"]: c for c in BENCH["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    assert config["name"] == w["config"]
    assert config["reduced"] == configs[w["config"]]["reduced"]
    mix = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    assert (HERE / "drivers" / f"{mix['kind']}.py").is_file()
    assert w["chips"] in (1, 4)
    e2e = [m for m in BENCH["end_to_end"] if _reports(cell, m)]
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    layers = [m for m in BENCH["per_layer"] if _reports(cell, m)]
    assert layers
    for m in layers:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()


def test_names_units_and_cross_references():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += list(CELLS) + [c["name"] for c in BENCH["configs"]]
    names += [w["traffic"] for w in CELLS.values()] + [w["config"] for w in CELLS.values()]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    for name in names:
        assert NAME.fullmatch(name), name
    texts = [x["why"] for x in BENCH["configs"] + BENCH["workloads"]]
    texts += [c["source"] for c in BENCH["configs"]] + BENCH["command"]
    texts += [m["layer"] for m in BENCH["per_layer"]]
    for text in texts:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text, text
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
    pairs = [(w["config"], w["traffic"]) for w in CELLS.values()]
    assert len(pairs) == len(set(pairs))
    assert {c["name"] for c in BENCH["configs"]} == {w["config"] for w in CELLS.values()}
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
        assert m["moves"] in E2E
        for cell in m["workloads"]:
            assert cell in CELLS
            assert _reports(cell, E2E[m["moves"]]), (m["name"], cell)
    for path in (HERE / "metrics").glob("*.py"):
        assert path.stem in {m["name"] for m in BENCH["per_layer"]}, path.name
