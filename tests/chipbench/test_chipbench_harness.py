"""The harness end to end on the CPU at a tiny size: a sound run is
correct, its result line has the keys the benchmark format names,
nothing compiles inside the window, and without a TPU ``run.py`` exits
non-zero and prints no result."""

import json
import os
import subprocess
import sys

import pytest
from chipbench_tiny import ROOT, run_tiny, tiny_arch

KEYS = ["correct", "attempted", "failed", "metrics", "device", "check"]


@pytest.fixture(scope="module", autouse=True)
def _arch():
    with tiny_arch():
        yield


@pytest.mark.parametrize("seed", [7, 2 ** 32 + 3])
def test_sound_run_is_correct(seed):
    from chipbench_tiny import tiny_cell
    out, logs = run_tiny(seed=seed)
    assert list(out) == KEYS
    assert out["correct"] is True, out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in
                                   tiny_cell()["end_to_end"]}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["check"]["data_gap"]["value"] == 0
    window = [m for m in logs if m.startswith("[window]")]
    assert window and "compiles in the window: 0 " in window[0], window


def test_seeds_share_one_federation():
    """Two seeds run the same federation from the same weights: the same
    clients and samples, and round for round the same cohorts."""
    from chipbench import harness
    from chipbench_tiny import tiny_cell
    keep = [{}, {}]
    for k, seed in enumerate((21, 22)):
        run_tiny(seed=seed, keep=keep[k])
    a, b = (k["trainer"].clients for k in keep)
    assert all((x.x == y.x).all() and (x.y == y.y).all() for x, y in zip(a, b))
    cfg = tiny_cell()["config_data"]
    assert harness.fl_config(cfg).seed == cfg["federation_seed"]
    u, v = (k["round_updates"] for k in keep)
    n = min(len(u), len(v))
    assert n >= 2 and u[:n] == v[:n]




def test_traced_run_keys(tmp_path, monkeypatch):
    from chipbench import spec
    peaks = spec.peaks
    monkeypatch.setattr(spec, "peaks", lambda kind: peaks("TPU v5 lite"))
    out, _ = run_tiny(seconds=4.0, traced=True, tmp_path=tmp_path)
    assert list(out) == KEYS[:5] + ["breakdown", "check"]
    assert out["correct"] is True
    assert {"select_ms", "train_host_ms", "mfu"} <= set(out["metrics"])
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "cnn-mnist.sync", "--seed", "3000000001",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "TPU" in p.stderr


def test_result_line_is_last_and_json(tmp_path):
    out, _ = run_tiny(seed=3_000_000_019)
    line = json.dumps(out)
    assert json.loads(line)["check"] == out["check"]
    assert list(json.loads(line))[-1] == "check"


@pytest.mark.parametrize("field,value", [("samples_per_client", 1199),
                                         ("local_steps", 119),
                                         ("n_params", 6795)])
def test_trainer_that_differs_from_the_config_fails_the_run(field, value):
    from chipbench import harness
    from chipbench_tiny import tiny_cell

    from repro.fl.client import build_fl_clients
    cfg = tiny_cell()["config_data"]
    fl = harness.fl_config(cfg)
    trainer = build_fl_clients(cfg["arch"], fl, dataset=cfg["dataset"],
                               scale=cfg["data_scale"])
    weights = harness.make_weights(cfg, 5)
    harness.check_trainer(cfg, trainer, weights)
    with pytest.raises(harness.SetupError):
        harness.check_trainer(dict(cfg, **{field: value}), trainer, weights)
