"""BENCHMARK.json against the rules the benchmark is built to, and every
name in it found as a file under ``bench/``."""
import json
import math
import re

import pytest

from bench.lib import layout

B = layout.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                    r"projection|head|expansion|per_tok")
CELLS = [w["name"] for w in B["workloads"]]


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys_command_and_paths():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"]
    assert len(json.dumps(B)) < 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells():
    runs = 2 + 14 * 24
    assert 1 <= B["run_seconds"] <= 51
    assert runs * (B["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cfg", B["configs"], ids=lambda c: c["name"])
def test_config_files_hold_what_they_claim(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and cfg["file"].startswith("bench/")
    body = json.loads((layout.ROOT / cfg["file"]).read_text())
    for key in cfg["reduced"]:
        assert NAME.match(key) and not WIDTHS.search(key)
        assert key in body and key in body["reduced"]
    assert any(w["config"] == cfg["name"] for w in B["workloads"])
    assert (layout.BENCH / "configs" / f"{body['reference']}.py").exists()


@pytest.mark.parametrize("cell", B["workloads"], ids=lambda w: w["name"])
def test_every_cell_is_well_formed_and_reports_enough(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert (layout.BENCH / "traffic" / f"{cell['traffic']}.json").exists()
    e2e = [m["name"] for m in B["end_to_end"] if _reports(m, cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(_reports(m, cell["name"]) for m in B["per_layer"])


def test_cells_are_unique_and_few_take_four_chips():
    assert len(set(CELLS)) == len(CELLS)
    pairs = {(w["config"], w["traffic"]) for w in B["workloads"]}
    assert len(pairs) == len(CELLS)
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(1, math.floor(len(CELLS) / 2))


@pytest.mark.parametrize("m", B["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metrics(m):
    assert set(m) <= {"name", "unit", "better", "bound", "source",
                      "workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25
    assert set(m.get("workloads", CELLS)) <= set(CELLS)
    assert (layout.BENCH / "metrics" / f"{m['name']}.py").exists()


@pytest.mark.parametrize("m", B["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metrics_move_a_metric_their_cells_report(m):
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    moved = next(e for e in B["end_to_end"] if e["name"] == m["moves"])
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS and _reports(moved, cell)
    if m["name"].endswith("_roofline"):
        assert m["unit"] == "%"
    assert (layout.BENCH / "metrics" / f"{m['name']}.py").exists()


def test_names_are_unique_across_metrics():
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(set(names)) == len(names)


def test_metrics_of_a_cell_follow_the_trace_flag():
    cell = CELLS[0]
    e2e = {m["name"] for m in layout.metrics_of(B, cell, trace=False)}
    per = {m["name"] for m in layout.metrics_of(B, cell, trace=True)}
    assert "setup_s" in e2e and not e2e & per
