"""The ``eval_device_ms`` reader on synthetic device reductions: the
evaluation program's device milliseconds per round on the busiest chip,
and ``None`` where no evaluation program ran."""

import pytest
from chipbench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from chipbench import harness, spec

MS = 1_000_000                      # ns

# two chips' programs in the traced part, ns
DEVICES = [
    {"modules_ns": {"jit__batch_train_impl": 400 * MS,
                    "jit__gather_batches": 3 * MS,
                    "jit__fedagg_call": 1 * MS,
                    "jit__eval_impl": 36 * MS}},
    {"modules_ns": {"jit__eval_impl": 12 * MS}},
]


def context(devices=DEVICES, rounds=4):
    return harness.Context(cell={}, rounds=rounds, window_s=2.0,
                           round_updates=[4] * rounds, spans=[],
                           devices=devices, peaks={}, chips=len(devices),
                           n_params=77_594)


def test_eval_device_ms_per_round_on_the_busiest_chip():
    """36 ms of ``_eval_impl`` on the busier chip over 4 rounds; the
    training, gather and merge programs belong to other readers."""
    got = spec.metric_reader("eval_device_ms").read(context())
    assert got == pytest.approx(9.0)


@pytest.mark.parametrize("devices,rounds", [
    ([{"modules_ns": {"jit__batch_train_impl": MS}}], 4),
    ([], 4),
    (DEVICES, 0)])
def test_eval_device_ms_none_without_its_program(devices, rounds):
    assert spec.metric_reader("eval_device_ms").read(
        context(devices, rounds)) is None
