"""The benchmark's copy of the wireless delay model draws what the
program's model draws, and its vectorized cohort sampler is bit-equal
to the per-call ``default_rng`` path."""

import numpy as np
import pytest
from chipbench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from chipbench.network import WirelessNetwork


@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_vectorized_equals_per_call(seed):
    net = WirelessNetwork(50, [5.0, 10.0, 15.0, 20.0, 25.0], 2.0, 0.1,
                          (30.0, 60.0), seed)
    clients = np.arange(50)
    got = net.delays(clients, 17)
    want = [net.delay(int(c), 17) for c in clients]
    assert np.array_equal(got, np.asarray(want))


def test_matches_the_program_today():
    from repro.fl.network import WirelessNetwork as Program
    args = (50, [5.0, 10.0, 15.0, 20.0, 25.0], 2.0, 0.1, (30.0, 60.0), 99)
    ours, theirs = WirelessNetwork(*args), Program(*args)
    for rnd in (0, 1, 250):
        assert np.array_equal(ours.delays(np.arange(50), rnd),
                              theirs.delays(np.arange(50), rnd))
