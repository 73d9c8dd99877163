"""The benchmark's copy of the clients' image data draws what the
program's generator and partition draw today, and ``data_gap`` counts
the clients whose samples differ."""

import numpy as np
import pytest
from chipbench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from chipbench.datasets import images as data


@pytest.mark.parametrize("seed,scale", [(0, 0.02), (7, 0.05),
                                        (2 ** 31 + 5, 0.02)])
def test_train_set_matches_the_program(seed, scale):
    from repro.data.synthetic import make_image_dataset
    theirs = make_image_dataset("mnist", seed=seed, scale=scale)
    x, y = data.train_set("mnist", seed, scale)
    assert x.dtype == theirs["x_train"].dtype and y.dtype == \
        theirs["y_train"].dtype
    assert np.array_equal(x, theirs["x_train"])
    assert np.array_equal(y, theirs["y_train"])


@pytest.mark.parametrize("n_clients,primary_frac", [(10, 0.7), (7, 0.9),
                                                    (10, 0.05)])
def test_partition_matches_the_program(n_clients, primary_frac):
    from repro.data.partition import primary_class_partition
    _, y = data.train_set("mnist", 3, 0.02)
    ours = data.partition(y, n_clients, primary_frac, 3)
    theirs = primary_class_partition(y, n_clients, primary_frac, seed=3)
    assert len(ours) == len(theirs)
    assert all(np.array_equal(a, b) for a, b in zip(ours, theirs))


class _Client:
    def __init__(self, x, y):
        self.x, self.y = x, y


class _Trainer:
    def __init__(self, clients):
        self.clients = clients


def test_data_gap_counts_clients_that_differ():
    cfg = {"dataset": "mnist", "federation_seed": 4, "data_scale": 0.02,
           "federation": {"n_clients": 6, "primary_frac": 0.7}}
    ours = data.clients(cfg)
    same = [_Client(x.copy(), y.copy()) for x, y in ours]
    assert data.data_gap(_Trainer(same), ours) == 0
    swapped = same[1:2] + same[:1] + same[2:]
    assert data.data_gap(_Trainer(swapped), ours) == 2
    assert data.data_gap(_Trainer(same[:5]), ours) == 1
    same[3].x[0, 0, 0, 0] += 1e-6
    assert data.data_gap(_Trainer(same), ours) == 1
