"""``BENCHMARK.json`` and the files it names hold together: every cell
names an existing configuration, traffic mix and limits file; every
per-layer metric has its reader, lists existing cells and moves an
end-to-end metric those cells report; names, units and the run length
keep to the limits the benchmark format sets."""

import importlib
import json
import re

import pytest
from chipbench_tiny import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
WIDTHS = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                    r"projection|head|expansion|experts_per_token|channels|fc")


def reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells at this length fits a 12-hour budget
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_and_units():
    names = ([c["name"] for c in BENCH["configs"]] + list(CELLS)
             + list(E2E) + [m["name"] for m in BENCH["per_layer"]])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in CELLS.values()]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    path = ROOT / cfg["file"]
    assert path.is_file() and cfg["file"].startswith("chipbench/")
    data = json.loads(path.read_text())
    assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"]
    assert not [k for k in cfg["reduced"] if WIDTHS.search(k)]
    assert (ROOT / "chipbench" / "models" / f"{data['model']}.py").is_file()
    assert any(w["config"] == cfg["name"] for w in CELLS.values())


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cells(cell):
    w = CELLS[cell]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["config"] in {c["name"] for c in BENCH["configs"]}
    assert (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").is_file()
    limits = json.loads((ROOT / "chipbench" / "cells" / f"{cell}.json")
                        .read_text())["limits"]
    assert limits and all(v >= 0 for v in limits.values())
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    e2e = [m for m in BENCH["end_to_end"] if reports(m, cell)]
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert [m for m in BENCH["per_layer"] if reports(m, cell)]
    assert len({(x["config"], x["traffic"]) for x in CELLS.values()}) == \
        len(CELLS)


def test_at_most_half_the_cells_take_four_chips():
    four = sum(1 for w in CELLS.values() if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 2)


def test_end_to_end_bounds():
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for m in E2E.values():
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    mod = importlib.import_module(f"chipbench.metrics.{metric['name']}")
    assert callable(mod.read)
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        metric["unit"], metric["layer"], metric["moves"])
    assert metric["moves"] in E2E
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        assert reports(E2E[metric["moves"]], cell)
    assert metric["name"].endswith("_roofline") == (metric["unit"] == "%"
                                                    and "roofline" in
                                                    metric["name"])


def test_layers_spelled_alike():
    by_layer = {}
    for m in BENCH["per_layer"]:
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())
