"""The sync cell reads what it read before the harness took other
traffic and other configurations: on a clock that moves a tenth of a
second at each of the trainer's evaluations (once a round), the tiny
``cnn-mnist.sync`` run captures the same rounds, and its check numbers
equal the values pinned here, which the harness gave while it knew only
sync rounds on image data."""

import pytest
from chipbench_tiny import run_tiny, tiny_arch

from repro.fl.client import CNNTrainer

# seed -> the check numbers of a 1-s window of ten rounds
PINNED = {
    7: {"data_gap": 0.0, "train_gap_median": 2.2089885267520206e-07,
        "merge_gap": 5.899017782077856e-08},
    2 ** 32 + 3: {"data_gap": 0.0, "train_gap_median": 2.485407487060984e-07,
                  "merge_gap": 7.115505660652606e-08},
}
# the window's distinct client updates, round by round (every seed runs
# the configuration's one federation)
ROUND_UPDATES = [2, 0, 1, 1, 2, 4, 0, 0, 3, 1]


class RoundClock:
    """``time`` for the harness: stands still inside a round."""

    def __init__(self):
        self.t = 1000.0

    def perf_counter(self):
        return self.t


@pytest.fixture(scope="module", autouse=True)
def _arch():
    with tiny_arch():
        yield


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_sync_check_numbers_are_pinned(seed, monkeypatch):
    from chipbench import harness
    clock = RoundClock()
    evaluate = CNNTrainer.evaluate

    def ticking(self, params, *a, **kw):
        clock.t += 0.1
        return evaluate(self, params, *a, **kw)

    monkeypatch.setattr(harness, "time", clock)
    monkeypatch.setattr(CNNTrainer, "evaluate", ticking)
    keep = {}
    out, _ = run_tiny(seed=seed, seconds=1.0, keep=keep)
    got = {k: c["value"] for k, c in out["check"].items()}
    assert keep["round_updates"] == ROUND_UPDATES
    assert out["correct"] is True
    assert got == PINNED[seed]
