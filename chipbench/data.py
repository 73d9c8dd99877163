"""The data module a configuration names: ``cfg["data"]`` is a module
under ``chipbench/datasets/`` (its docstring lists what each gives)."""

from __future__ import annotations

import importlib


def module(cfg: dict):
    return importlib.import_module(f"chipbench.datasets.{cfg['data']}")
