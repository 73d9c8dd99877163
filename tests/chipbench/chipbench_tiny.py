"""A cell of the benchmark cut to a size a CPU test run can hold.

The real cells run the paper's models at full width on a TPU; here the
same harness, reference and limits run a two-conv CNN of 6,794 params
over 10 clients of 1,200 synthetic MNIST samples (120 local
steps, as in the real cell), with the Pallas merge
in interpret mode.  ``tiny_arch`` registers that model with the
program for the length of a test module."""

from __future__ import annotations

import contextlib
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_SIZES = {"input_hw": [28, 28, 1], "kernel": 3, "channels": [4, 8],
              "fc": [16, 10], "n_classes": 10}
TINY_PARAMS = 6794


@contextlib.contextmanager
def tiny_arch():
    import pytest

    from repro.config import base
    base._ensure_loaded()
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(base._REGISTRY, "cnn-tiny", lambda: base.ModelConfig(
            arch_id="cnn-tiny", family="cnn", cnn_channels=(4, 8),
            cnn_fc=(16, 10), input_hw=(28, 28, 1), n_classes=10))
        yield


def tiny_cell(name: str = "cnn-mnist.sync") -> dict:
    """``name`` cut to the tiny size, with its own limits."""
    from chipbench import spec
    cell = spec.cell(name)
    cfg = cell["config_data"]
    cfg.update(arch="cnn-tiny", model="cnn", dataset="mnist", data_scale=0.2,
               sizes=dict(TINY_SIZES), n_params=TINY_PARAMS, n_leaves=8,
               samples_per_client=1200, local_steps=120)
    cfg["federation"].update(n_clients=10, tau=3, n_tiers=2,
                             tier_delay_means=[5.0, 10.0])
    return cell


def run_tiny(name: str = "cnn-mnist.sync", seed: int = 7,
             seconds: float = 1.5, traced: bool = False, tmp_path=None,
             keep=None):
    import time

    from chipbench import harness
    logs = []
    out = harness.run(name, seed, seconds, traced, time.perf_counter(),
                      logs.append, cell=tiny_cell(name), require_tpu=False,
                      trace_dir=str(tmp_path) if traced else None, keep=keep)
    return out, logs
