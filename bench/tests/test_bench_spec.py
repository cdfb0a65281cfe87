"""BENCHMARK.json against the contract the harness is built to."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {w["name"]: w for w in SPEC["workloads"]}
E2E = {m["name"]: m for m in SPEC["end_to_end"]}


def _reports(metric: dict) -> list[str]:
    return metric.get("workloads", list(CELLS))


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) <= 64 * 1024
    assert all(not w.startswith("/") and ".." not in w
               for w in SPEC["command"])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_resolves_to_its_files(cell):
    from bench import run

    w = CELLS[cell]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    loaded = run.load_cell(cell)
    assert loaded["chips"] == w["chips"]
    assert loaded["config"]["chips"] == w["chips"]
    assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in loaded["end_to_end"] + loaded["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m}.py").is_file(), m
    assert "setup_s" in loaded["end_to_end"]
    assert len(loaded["end_to_end"]) >= 2 and loaded["per_layer"]


def test_configs_are_files_under_paths_and_used():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and c["name"] in used
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg), c["reduced"]
        assert cfg["reduced"] == c["reduced"]


def test_names_and_units_use_legal_characters():
    names = [c["name"] for c in SPEC["configs"]] + list(CELLS) \
        + [w["traffic"] for w in SPEC["workloads"]] \
        + [k for c in SPEC["configs"] for k in c["reduced"]]
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names += [m["name"] for m in metrics]
    for n in names:
        assert NAME.match(n), n
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")


def test_end_to_end_bounds_and_sources():
    assert "setup_s" in E2E
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_per_layer_moves_a_metric_all_its_cells_report():
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
        moved = E2E[m["moves"]]
        for cell in m.get("workloads", list(CELLS)):
            assert cell in CELLS, cell
            assert cell in _reports(moved), (m["name"], cell)
        if m["unit"] == "%" and m["name"].endswith("_roofline"):
            assert m["better"] == "higher"


def test_at_most_half_the_cells_ask_for_four_chips():
    four = sum(1 for w in SPEC["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 2)


def test_every_cell_has_a_metric_of_each_kind():
    for cell in CELLS:
        e2e = [m for m in SPEC["end_to_end"] if cell in _reports(m)]
        layer = [m for m in SPEC["per_layer"] if cell in _reports(m)]
        assert any(m["name"] == "setup_s" for m in e2e)
        assert len(e2e) >= 2 and layer, cell


SHARDED = [c for c in SPEC["configs"]
           if json.loads((ROOT / c["file"]).read_text())["executor"]["kind"]
           == "sharded"]


@pytest.mark.parametrize("config", [c["name"] for c in SHARDED])
def test_a_sharded_mesh_spans_the_chips_of_its_cells(config):
    (c,) = [c for c in SHARDED if c["name"] == config]
    cfg = json.loads((ROOT / c["file"]).read_text())
    ex = cfg["executor"]
    assert ex["switches"] * ex["ports"] == cfg["chips"]
    for w in SPEC["workloads"]:
        if w["config"] == config:
            assert w["chips"] == cfg["chips"], w["name"]
