"""The semi-async cell ``cnn-mnist.async`` end to end on the CPU at a
tiny size: a sound run is correct and compiles nothing in the window;
the runner it drives, run to its end, used the dense client-state store
and the folded Pallas merge; and the staleness the harness derives from
the selections it sees equals the runner's own."""

import pytest
from chipbench_tiny import run_tiny, tiny_arch, tiny_cell

CELL = "cnn-mnist.async"


@pytest.fixture(scope="module", autouse=True)
def _arch():
    with tiny_arch():
        yield


@pytest.mark.parametrize("seed", [7, 2 ** 32 + 3])
def test_sound_async_run_is_correct(seed):
    out, logs = run_tiny(CELL, seed=seed, seconds=2.0)
    assert out["correct"] is True, out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["check"]) == {"data_gap", "start_gap", "train_gap_median",
                                 "merge_gap"}
    assert out["check"]["start_gap"]["value"] == 0
    window = [m for m in logs if m.startswith("[window]")]
    assert window and "compiles in the window: 0 " in window[0], window


def test_async_traffic_runs_on_the_dense_store_and_fold_kernel(monkeypatch):
    """The traffic's runner and keyword arguments, run to their end over
    the tiny federation: every multi-row window merged through
    ``fedagg_fold`` from the dense store."""
    import repro.core.state
    from chipbench import data, harness
    from chipbench.network import WirelessNetwork

    from repro.core import run_method
    cell = tiny_cell(CELL)
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    folds = []
    fold = repro.core.state.fedagg_fold_pytree

    def counting(g, stacked, coef, **kw):
        folds.append(len(coef) - 1)
        return fold(g, stacked, coef, **kw)

    monkeypatch.setattr(repro.core.state, "fedagg_fold_pytree", counting)
    fl = harness.fl_config(cfg, rounds=6, traffic=traffic)
    fed = cfg["federation"]
    net = WirelessNetwork(fed["n_clients"], fed["tier_delay_means"],
                          fed["delay_std"], fed["mu"],
                          tuple(fed["failure_delay"]), cfg["federation_seed"])
    trainer = data.module(cfg).build_trainer(cfg, fl)
    hist = run_method(traffic["method"], trainer, net, fl, **traffic["runner"])
    assert hist.meta["store_path"] == "store"
    assert hist.meta["residency"] == "dense"
    assert hist.meta["kernel_agg"] is True
    assert hist.meta["alpha"] == 0.6 and hist.meta["a"] == 0.5
    assert len(hist.rounds) == 6
    assert folds and all(k >= 2 for k in folds)


def test_derived_staleness_equals_the_runners(tmp_path, monkeypatch):
    """The staleness of every merged client, from the rounds the
    network saw each selected in and the updates merged before them,
    equals the ``fl.staleness`` histogram the runner records, merge by
    merge."""
    from chipbench import spec
    peaks = spec.peaks
    monkeypatch.setattr(spec, "peaks", lambda kind: peaks("TPU v5 lite"))
    keep = {}
    out, _ = run_tiny(CELL, seed=19, seconds=2.0, traced=True,
                      tmp_path=tmp_path, keep=keep)
    assert out["correct"] is True, out["check"]
    theirs = keep["telemetry"].hists["fl.staleness"]
    assert keep["staleness"] == [int(s) for s in theirs]
    assert len(theirs) > 10 and max(theirs) > 0
    # the host spans of the trainer input are recorded on the cohort path
    assert {"select_ms", "batch_host_ms", "h2d_host_ms"} <= set(out["metrics"])


def test_recorder_derives_staleness_and_selection_globals():
    """Synthetic rounds: clients 3 and 5 selected in round 1, 3 merged
    there (its padded slot counted once); 7 selected in round 2 and
    merged there before the carried 5, which is two merges stale and
    keeps round 1's global as its start."""
    from chipbench import harness
    g0, g1 = ["g0"], ["g1"]
    rec = harness.RoundRecorder(None, g0, seconds=1.0, seed=0, track=True)
    rec.selected_now([3, 5])
    rec._record(harness.Call([3, 3], [1, 1], None, None, shared=False))
    assert [m.staleness for m in rec.round_merged] == [0]
    rec.round_merged, rec.last_params, rec.n_rounds = [], g1, 1
    rec._next_round(1, g0)
    assert rec.v_start == {1: 0}          # 5 is still in flight
    rec.selected_now([7])
    rec._record(harness.Call([7, 5], [2, 3], None, None, shared=False))
    assert rec.staleness == [0, 0, 2]
    fresh, carried = rec.round_merged
    assert (fresh.at, fresh.before) == (g1, g0)
    assert (carried.at, carried.before) == (g0, None)
    rec._next_round(2, g1)
    assert rec.version == 3 and rec.v_start == {} and rec.g_start == {}
