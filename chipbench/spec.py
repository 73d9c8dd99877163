"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

Each configuration is ``configs/<config>.json`` with its plain
reference model in ``models/<model>.py`` and its data in
``datasets/<data>.py``; each traffic mix is ``traffic/<traffic>.json``;
each cell's correctness limits are ``cells/<cell>.json``; each
per-layer metric is ``metrics/<name>.py``; each runner's warm-up is
``warmups/<method>.py``.
The harness finds every file by the name ``BENCHMARK.json`` gives, so
a new cell, mix, configuration or metric is a new file and an entry,
and no edit of a file that is there.
"""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

PKG = Path(__file__).resolve().parent
ROOT = PKG.parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(PKG / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(PKG / "traffic" / f"{name}.json")


def limits(cell_name: str) -> dict:
    return load_json(PKG / "cells" / f"{cell_name}.json")


def metric_reader(name: str):
    return importlib.import_module(f"chipbench.metrics.{name}")


def warm_up(method: str):
    return importlib.import_module(f"chipbench.warmups.{method}")


def peaks(device_kind: str) -> dict:
    table = load_json(PKG / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def cell(name: str, bench: dict | None = None) -> dict:
    """The workload entry of ``name`` with its config, traffic, limits
    and the metrics it reports (``end_to_end`` and ``per_layer``)."""
    bench = bench or benchmark()
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = dict(found[0])

    def reports(m):
        return "workloads" not in m or name in m["workloads"]

    w["config_data"] = config(w["config"])
    w["traffic_data"] = traffic(w["traffic"])
    w["limits"] = limits(name)
    w["end_to_end"] = [m for m in bench["end_to_end"] if reports(m)]
    w["per_layer"] = [m for m in bench["per_layer"] if reports(m)]
    return w
