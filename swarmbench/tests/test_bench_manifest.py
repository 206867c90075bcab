"""BENCHMARK.json against the rules the harness and its checks rely on:
names and units, which metric moves which, the files each cell needs, and
the share of cells on four chips."""
import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def _cells_of(metric):
    return metric.get("workloads", list(CELLS))


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert BENCH["command"][:2] == ["python3", "swarmbench/run.py"]
    assert BENCH["paths"] == ["swarmbench"]


def test_every_name_and_unit_uses_the_allowed_characters():
    names = ([c["name"] for c in BENCH["configs"]] + list(CELLS)
             + list(E2E) + [m["name"] for m in BENCH["per_layer"]]
             + [w["traffic"] for w in CELLS.values()]
             + [w["config"] for w in CELLS.values()]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for text in ([w["why"] for w in CELLS.values()]
                 + [c["why"] for c in BENCH["configs"]]
                 + [c["source"] for c in BENCH["configs"]]
                 + [m["layer"] for m in BENCH["per_layer"]]):
        assert TEXT.match(text), text


def test_metric_sources_and_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25, m
    assert E2E["setup_s"]["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "bound" not in m


def test_every_moves_is_an_end_to_end_metric_each_of_its_cells_reports():
    for m in BENCH["per_layer"]:
        assert m["moves"] in E2E, m
        for cell in _cells_of(m):
            assert cell in CELLS, (m["name"], cell)
            assert cell in _cells_of(E2E[m["moves"]]), (m["name"], cell)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for cell in CELLS:
        e2e = [n for n, m in E2E.items() if cell in _cells_of(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(cell in _cells_of(m) for m in BENCH["per_layer"])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_file_a_cell_names_exists(cell):
    w = CELLS[cell]
    assert w["chips"] in (1, 4) and TEXT.match(w["why"])
    cfg = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert cfg["file"].startswith("swarmbench/")
    config = json.loads((ROOT / cfg["file"]).read_text())
    assert (ROOT / f"swarmbench/traffic/{w['traffic']}.json").is_file()
    limits = json.loads(
        (ROOT / f"swarmbench/limits/{cell}.json").read_text())
    # a number with no upper reading in a cell is not compared there
    assert {"gate_flips", "update_gap"} <= set(limits) <= {
        "gate_flips", "loss_gap", "mu_gap", "update_gap"}
    importlib.import_module(f"swarmbench.families.{config['family']}")
    for m in BENCH["per_layer"]:
        if cell in _cells_of(m):
            mod = importlib.import_module(f"swarmbench.metrics.{m['name']}")
            assert callable(mod.read)


def test_configurations_are_used_and_own_their_files():
    used = {w["config"] for w in CELLS.values()}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    pairs = [(w["config"], w["traffic"]) for w in CELLS.values()]
    assert len(set(pairs)) == len(pairs)


def test_four_chip_cells_are_at_most_half_or_one():
    four = sum(w["chips"] == 4 for w in CELLS.values())
    assert four <= max(1, len(CELLS) // 2)


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
