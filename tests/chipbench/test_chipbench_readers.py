"""The readers of the trainer-input and idle-attribution metrics on a
synthetic traced part with known spans and idle gaps: the exact
milliseconds per round, the average over chips, and ``None`` where the
spans they read are absent; the readers of the semi-async cell's
programs on synthetic device reductions; and a traced tiny run on the
CPU, whose result line carries the trainer-input metrics inside
``train_host_ms``."""

import pytest
from chipbench_tiny import ROOT, run_tiny, tiny_arch  # noqa: F401

from chipbench import harness, spec

MS = 1_000_000                      # ns
SHIFT = 110                         # round 2 starts 10 ms after round 1 ends

# one round, in ms from its start; round 2 is the same, SHIFT ms later
ROUND = [("round", 0, 100), ("round.select", 0, 10),
         ("round.train", 10, 50), ("train.batches", 12, 30),
         ("train.h2d", 30, 38), ("train.dispatch", 38, 45),
         ("round.aggregate", 50, 55), ("eval", 60, 90),
         ("trainer.local_train_batch", 11, 49),
         ("trainer.evaluate", 92, 99)]
# idle: 5-40 (select, train, input), 52-58 (aggregate 3, none 3),
# 95-100 (none: a trainer.* span is no attribution)
GAPS = [(5, 40), (52, 58), (95, 100)]
BETWEEN = [(100, SHIFT)]            # idle outside any round: not counted


def _ns(intervals, shift):
    return [(int((a + shift) * MS), int((b + shift) * MS))
            for a, b in intervals]


def context(spans=True, chips=1, idle=True):
    sp = [(n, int((a + s) * MS), int((b + s) * MS))
          for s in (0, SHIFT) for n, a, b in ROUND]
    sp.append(("run", 0, (2 * SHIFT) * MS))
    if not spans:
        sp = [x for x in sp if x[0].startswith("trainer.")]
    gaps = sorted(_ns(GAPS, 0) + _ns(BETWEEN, 0) + _ns(GAPS, SHIFT))
    devices = [{"gaps": gaps if idle else []}] + [{"gaps": []}] * (chips - 1)
    return harness.Context(cell={}, rounds=2, window_s=0.21,
                           round_updates=[3, 3], spans=sp, devices=devices,
                           peaks={}, chips=chips, n_params=0)


EXPECT = {"batch_host_ms": 18.0, "h2d_host_ms": 8.0,
          "idle_input_ms": 28.0, "idle_unattributed_ms": 8.0}
IDLE = ("idle_input_ms", "idle_unattributed_ms")


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_exact_ms_per_round(name):
    got = spec.metric_reader(name).read(context())
    assert got == pytest.approx(EXPECT[name], abs=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_none_without_its_spans(name):
    assert spec.metric_reader(name).read(context(spans=False)) is None


@pytest.mark.parametrize("name", IDLE)
def test_idle_reader_averages_over_chips(name):
    """Idle time on one of two chips counts half, as ``idle_share``
    averages the cell's chips."""
    got = spec.metric_reader(name).read(context(chips=2))
    assert got == pytest.approx(EXPECT[name] / 2, abs=1e-9)


@pytest.mark.parametrize("name", IDLE)
def test_idle_reader_zero_on_a_busy_device_and_none_without_one(name):
    reader = spec.metric_reader(name)
    assert reader.read(context(idle=False)) == 0
    ctx = context()
    ctx.devices = []
    assert reader.read(ctx) is None


def test_input_and_unattributed_split_the_round_idle():
    """The round's idle time (35 + 6 + 5 ms) is input (28), the other
    named phases (select 5, train before its input 2, aggregate 3) and
    the unattributed rest (8); the idle time between rounds is in none
    of them."""
    from chipbench import trace
    from chipbench.metrics import idle_input_ms, idle_unattributed_ms
    ctx = context()
    rounds = trace.union((a, b) for n, a, b in ctx.spans if n == "round")
    other = trace.union((a, b) for n, a, b in ctx.spans
                        if n in ("round.select", "round.train",
                                 "round.aggregate", "eval"))
    assert idle_input_ms.idle_ms_per_round(ctx, rounds) == pytest.approx(46)
    assert (idle_input_ms.idle_ms_per_round(ctx, other)
            == pytest.approx(28 + 5 + 2 + 3))
    assert (idle_input_ms.read(ctx) + 10 + idle_unattributed_ms.read(ctx)
            == pytest.approx(46))
    assert idle_input_ms.intersect([(0, 10), (20, 30)],
                                   [(5, 25), (28, 40)]) == [
        (5, 10), (20, 25), (28, 30)]


def test_traced_run_reports_trainer_input(tmp_path, monkeypatch):
    """The child spans lie inside ``round.train``: their host time per
    round is at most ``train_host_ms``."""
    peaks = spec.peaks
    monkeypatch.setattr(spec, "peaks", lambda kind: peaks("TPU v5 lite"))
    with tiny_arch():
        out, _ = run_tiny(seconds=4.0, traced=True, tmp_path=tmp_path)
    assert out["correct"] is True
    m = out["metrics"]
    assert {"train_host_ms", "batch_host_ms", "h2d_host_ms"} <= set(m)
    assert (m["batch_host_ms"]["value"] + m["h2d_host_ms"]["value"]
            <= m["train_host_ms"]["value"])


# the semi-async cell's programs on two chips, ns in the traced part
ASYNC_DEVICES = [
    {"modules_ns": {"jit__batch_train_multi_impl": 30 * MS,
                    "jit__batch_train_impl": 4 * MS,
                    "jit__gather_batches": 2 * MS,
                    "jit_gather_impl": 3 * MS, "jit_gather_one_impl": 1 * MS,
                    "jit_scatter_params_impl": 2 * MS,
                    "jit__fedagg_fold_call": 1 * MS, "jit__eval_impl": 9 * MS},
     "custom_call_ns": {"jit__fedagg_fold_call": 800_000,
                        "jit__fedagg_call": 5 * MS}},
    {"modules_ns": {"jit__batch_train_multi_impl": 10 * MS},
     "custom_call_ns": {"jit__fedagg_fold_call": 400_000}},
]


def async_context(devices=ASYNC_DEVICES, round_updates=(3, 1, 0, 2)):
    return harness.Context(cell={}, rounds=4, window_s=0.5,
                           round_updates=list(round_updates), spans=[],
                           devices=devices, peaks={"hbm_bytes_per_s": 819e9},
                           chips=len(devices), n_params=1_000_000)


def test_train_and_store_device_ms_per_round():
    """The cohort and singleton training programs (34 ms) and the
    store's gathers and scatters (6 ms) over 4 rounds, on the busiest
    chip; the batch gather, the fold and ``evaluate`` belong to
    neither."""
    ctx = async_context()
    assert spec.metric_reader("train_device_ms").read(ctx) == \
        pytest.approx(8.5)
    assert spec.metric_reader("store_device_ms").read(ctx) == \
        pytest.approx(1.5)


def test_fold_roofline_counts_fold_rounds_over_fold_kernel_time():
    """Rounds of 3 and 2 rows fold (5 + 4 rows of 4 MB read or written);
    the round of one row and the empty one do not; the kernel time is
    the busiest chip's custom calls inside the fold programs."""
    got = spec.metric_reader("fold_roofline").read(async_context())
    need = (5 + 4) * 1_000_000 * 4
    assert got == pytest.approx(100 * need / (800_000e-9 * 819e9))
    assert 0 < got <= 100


@pytest.mark.parametrize("name", ["train_device_ms", "store_device_ms",
                                  "fold_roofline"])
def test_async_reader_none_without_its_programs(name):
    empty = [{"modules_ns": {"jit__eval_impl": MS}, "custom_call_ns": {}}]
    reader = spec.metric_reader(name)
    assert reader.read(async_context(devices=empty)) is None
    assert reader.read(async_context(devices=[])) is None
