"""``correct`` comes out false when the timed path is broken underneath
the harness, once for each fault the cell can have (a client's training
returns its start; half of each batch left out; half of the cohort left
out of the merge; an answer altered where it is produced; the clients
given other samples), and the control
(the reference in bfloat16 put in the program's place) fails at least
one of the cell's limits.  Tiny size on the CPU; the harness's look for
a chip is skipped, the rest of a run is driven as on the chip."""

import jax
import jax.numpy as jnp
import pytest
from chipbench_tiny import run_tiny, tiny_arch, tiny_cell

import repro.fl.client
from repro.core.engine import BatchedClientEngine
from repro.fl.client import CNNTrainer


@pytest.fixture(scope="module", autouse=True)
def _arch():
    with tiny_arch():
        yield


def _unchanged(self, params, xs, ys):
    return jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l, (xs.shape[0],) + l.shape), params)


_train = CNNTrainer._batch_train_impl


def _half_batch(self, params, xs, ys):
    return _train(self, params, xs[:, :, :xs.shape[2] // 2],
                  ys[:, :, :ys.shape[2] // 2])


_agg = BatchedClientEngine.aggregate_or_keep


def _half_cohort(self, params, stacked, weights):
    k = len(weights) - len(weights) // 2
    return _agg(self, params, jax.tree_util.tree_map(lambda l: l[:k], stacked),
                weights[:k])


def _altered(self, params, stacked, weights):
    out = _agg(self, params, stacked, weights)
    leaves, tree = jax.tree_util.tree_flatten(out)
    big = max(range(len(leaves)), key=lambda j: leaves[j].size)
    flat = leaves[big].reshape(-1)
    leaves[big] = flat.at[0].add(0.01 * jnp.max(jnp.abs(flat))).reshape(
        leaves[big].shape)
    return jax.tree_util.tree_unflatten(tree, leaves)


_partition = repro.fl.client.primary_class_partition


def _other_samples(*args, **kw):
    parts = _partition(*args, **kw)
    return parts[1:] + parts[:1]


FAULTS = {
    "unchanged": [(CNNTrainer, "_batch_train_impl", _unchanged)],
    "half_batch": [(CNNTrainer, "_batch_train_impl", _half_batch)],
    "half_cohort": [(BatchedClientEngine, "aggregate_or_keep", _half_cohort)],
    "altered": [(BatchedClientEngine, "aggregate_or_keep", _altered)],
    "other_samples": [(repro.fl.client, "primary_class_partition",
                       _other_samples)],
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    for owner, name, fn in FAULTS[fault]:
        monkeypatch.setattr(owner, name, fn)
    out, _ = run_tiny(seed=11)
    assert out["correct"] is False, (fault, out["check"])
    assert any(c["value"] > c["limit"] for c in out["check"].values())


@pytest.mark.parametrize("seed", [13, 2 ** 31 + 13])
def test_control_fails_a_limit(seed):
    """At this size the control's training stays under ``train_gap_median``'s
    limit, so it fails ``merge_gap``: that needs a captured round that
    merged two rows or more, and a window long enough to hold one on a
    loaded CPU."""
    from chipbench import calibrate
    cell = tiny_cell()
    limits = cell["limits"]["limits"]
    (line,) = calibrate.calibrate(cell, [seed], 5.0, ["control"],
                                  lambda m: None, require_tpu=False)
    assert line["correct"] is True, line["program"]
    assert any(line["control"][k] > limits[k] for k in line["control"]), line
