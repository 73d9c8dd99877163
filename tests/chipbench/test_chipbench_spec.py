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


DATA_FUNCTIONS = ("build_trainer", "clients", "n_samples", "client_stream",
                  "data_gap", "check")
MODEL_FUNCTIONS = ("init", "forward", "loss", "flops_per_sample")


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_names_modules_that_provide_what_the_harness_calls(cfg):
    data = json.loads((ROOT / cfg["file"]).read_text())
    ds = importlib.import_module(f"chipbench.datasets.{data['data']}")
    model = importlib.import_module(f"chipbench.models.{data['model']}")
    for mod, names in ((ds, DATA_FUNCTIONS), (model, MODEL_FUNCTIONS)):
        missing = [n for n in names if not callable(getattr(mod, n, None))]
        assert not missing, (mod.__name__, missing)
    from chipbench import harness
    assert 1 <= harness.ref_block(data) <= harness.REF_CLIENTS


@pytest.mark.parametrize("traffic", sorted({w["traffic"]
                                            for w in CELLS.values()}))
def test_traffic_files(traffic):
    """Every mix names a runner the harness can warm, keyword arguments
    its warm-up covers, ``FLConfig`` fields that exist and a merge the
    reference knows."""
    import dataclasses

    from chipbench import harness
    from chipbench import reference as ref

    from repro.config.base import FLConfig
    t = json.loads((ROOT / "chipbench" / "traffic" / f"{traffic}.json")
                   .read_text())
    warm = harness.warm_up_module(t)
    assert t["name"] == traffic and callable(warm.warm)
    assert set(t.get("runner", {})) <= set(warm.RUNNER_KEYS)
    fields = {f.name for f in dataclasses.fields(FLConfig)}
    assert set(t.get("federation", {})) <= fields
    merge = t.get("merge", "sync_mean")
    assert merge in ("sync_mean", "staleness_fold")
    if merge == "staleness_fold":
        assert {"async_alpha", "async_staleness", "async_a"} <= set(
            t["federation"])
        assert ref.staleness_alphas(t["federation"], [0])


@pytest.mark.parametrize("traffic,fault", [
    ({"method": "fedbuff"}, "warmups/fedbuff.py is missing"),
    ({"method": "feddct", "warm_up": "feddct_tiered"},
     "warmups/feddct_tiered.py is missing"),
    ({"method": "feddct", "runner": {"use_kernel_agg": True,
                                     "store_capacity": 8}},
     "['store_capacity']")])
def test_warm_up_refuses_a_runner_it_cannot_warm(traffic, fault):
    from chipbench import harness
    with pytest.raises(harness.SetupError, match=re.escape(fault)):
        harness.warm_up({}, None, None, traffic)


def test_traffic_names_its_warm_up_and_its_runner_objects(monkeypatch):
    """A mix whose runner takes an option that a warm-up of its runner
    does not cover names a warm-up module of its own, which may build
    the runner's keyword arguments."""
    import types

    from chipbench import harness, spec
    seen = []
    mod = types.SimpleNamespace(
        RUNNER_KEYS=("mesh_chips",),
        warm=lambda cfg, traffic, trainer, params: seen.append(traffic),
        runner_kwargs=lambda t: {"mesh": ("mesh", t["runner"]["mesh_chips"])})
    monkeypatch.setattr(spec, "warm_up", lambda name: {"mesh4": mod}[name])
    t = {"method": "feddct_async", "warm_up": "mesh4",
         "runner": {"mesh_chips": 4}}
    harness.warm_up({}, None, None, t)
    assert seen == [t]
    assert harness.runner_kwargs(t) == {"mesh": ("mesh", 4)}


def test_runner_kwargs_default_to_the_traffic_runner():
    from chipbench import harness
    t = {"method": "feddct_async", "runner": {"use_kernel_agg": True}}
    assert harness.runner_kwargs(t) == {"use_kernel_agg": True}


@pytest.mark.parametrize("n_params,block", [(1_630_090, 8), (6_795, 8),
                                            (40_000_000, 6), (10 ** 9, 1)])
def test_reference_block_fits_its_adam_state(n_params, block):
    """As many of 8 clients per reference program as 4 GiB of Adam state
    (16 bytes a parameter a client) holds, and never none."""
    from chipbench import harness
    assert harness.ref_block({"n_params": n_params}) == block


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
