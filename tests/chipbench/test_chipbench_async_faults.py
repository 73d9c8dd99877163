"""``correct`` comes out false for the semi-async cell
``cnn-mnist.async`` when its timed path is broken underneath the
harness, once for each fault the cell can have: a client's training
returns its start; half of each batch left out; half of the window left
out of the fold, its mean taken over the rest; a merged weight altered
where it is produced; a client's start snapshot taken a round late; a
fold that ignores staleness.  And the control (the reference in
bfloat16) and the ``stale_start`` and ``no_staleness`` stand-ins, put in
the program's place, each fail a limit.  Tiny size on the CPU; the
harness's look for a chip is skipped, the rest of a run is driven as on
the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_tiny import run_tiny, tiny_arch, tiny_cell

import repro.core.state
import repro.runtime.async_loop
from repro.core.state import ClientStateStore
from repro.fl.client import CNNTrainer

CELL = "cnn-mnist.async"


@pytest.fixture(scope="module", autouse=True)
def _arch():
    with tiny_arch():
        yield


def _unchanged(self, params, xs, ys):
    return jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l, (xs.shape[0],) + l.shape), params)


def _unchanged_multi(self, starts, xs, ys):
    return starts


_train = CNNTrainer._batch_train_impl
_train_multi = CNNTrainer._batch_train_multi_impl


def _half_batch(self, params, xs, ys):
    return _train(self, params, xs[:, :, :xs.shape[2] // 2],
                  ys[:, :, :ys.shape[2] // 2])


def _half_batch_multi(self, starts, xs, ys):
    return _train_multi(self, starts, xs[:, :, :xs.shape[2] // 2],
                        ys[:, :, :ys.shape[2] // 2])


_fold = repro.core.state.fedagg_fold_pytree


def _fold_half_cohort(g, stacked, coef, **kw):
    c = np.array(coef)
    real = np.flatnonzero(c[1:] > 0) + 1
    c[real[len(real) - len(real) // 2:]] = 0
    return _fold(g, stacked, jnp.asarray(c), **kw)


def _fold_altered(g, stacked, coef, **kw):
    leaves, tree = jax.tree_util.tree_flatten(_fold(g, stacked, coef, **kw))
    big = max(range(len(leaves)), key=lambda j: leaves[j].size)
    flat = leaves[big].reshape(-1)
    leaves[big] = flat.at[0].add(0.01 * jnp.max(jnp.abs(flat))).reshape(
        leaves[big].shape)
    return jax.tree_util.tree_unflatten(tree, leaves)


_merge_window_store = repro.runtime.async_loop._merge_window_store
_scatter_params = ClientStateStore.scatter_params


def _merging(eng, store, *args, **kw):
    store.merging = True
    try:
        return _merge_window_store(eng, store, *args, **kw)
    finally:
        store.merging = False


def _lagged_snapshot(self, ids, params):
    """The selection's snapshot writes the global the previous
    selection saw: a start from the wrong round."""
    if getattr(self, "merging", False):
        return _scatter_params(self, ids, params)
    prev = getattr(self, "prev_selection", params)
    self.prev_selection = params
    return _scatter_params(self, ids, prev)


def _no_staleness(fl, stalenesses):
    return [fl.async_alpha] * len(stalenesses)


# fault -> (patches, the number it has to fail)
FAULTS = {
    "unchanged": ([(CNNTrainer, "_batch_train_impl", _unchanged),
                   (CNNTrainer, "_batch_train_multi_impl", _unchanged_multi)],
                  "train_gap_median"),
    "half_batch": ([(CNNTrainer, "_batch_train_impl", _half_batch),
                    (CNNTrainer, "_batch_train_multi_impl",
                     _half_batch_multi)], "train_gap_median"),
    "half_cohort": ([(repro.core.state, "fedagg_fold_pytree",
                      _fold_half_cohort)], "merge_gap"),
    "altered": ([(repro.core.state, "fedagg_fold_pytree", _fold_altered)],
                "merge_gap"),
    "stale_start": ([(repro.runtime.async_loop, "_merge_window_store",
                      _merging),
                     (ClientStateStore, "scatter_params", _lagged_snapshot)],
                    "start_gap"),
    "no_staleness": ([(repro.runtime.async_loop, "_alphas", _no_staleness)],
                     "merge_gap"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_async_fault_is_not_correct(fault, monkeypatch):
    patches, number = FAULTS[fault]
    for owner, name, fn in patches:
        monkeypatch.setattr(owner, name, fn)
    out, _ = run_tiny(CELL, seed=11, seconds=2.0)
    assert out["correct"] is False, (fault, out["check"])
    assert out["check"][number]["value"] > out["check"][number]["limit"]


def test_async_control_and_stand_ins_fail_a_limit():
    """The bf16 control, a start from one round before the selection's
    and a fold that ignores staleness, each put in the program's place
    on the rounds one run captured, fail a limit."""
    from chipbench import calibrate
    cell = tiny_cell(CELL)
    limits = cell["limits"]["limits"]
    kinds = ["control", "stale_start", "no_staleness"]
    (line,) = calibrate.calibrate(cell, [17], 3.0, kinds, lambda m: None,
                                  require_tpu=False)
    assert line["correct"] is True, line["program"]
    for kind in kinds:
        assert any(line[kind][k] > limits[k] for k in line[kind]), (kind,
                                                                     line)
