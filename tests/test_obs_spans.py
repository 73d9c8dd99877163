"""Program spans of a sync FedDCT round on a real (reduced) CNN
trainer: each ``round`` holds its phases and the trainer's input
spans in order and carries the round's update count, tracing leaves
the history bit-identical, and every span lands on the JAX profiler's
host plane at the time ``tel.spans`` records."""

from __future__ import annotations

import glob
import os
import time

import jax.numpy as jnp
import pytest

from repro import obs
from repro.config import get_arch
from repro.config.base import FLConfig
from repro.core import run_method
from repro.fl.client import CNNTrainer
from repro.fl.network import WirelessNetwork

PHASES = ("round.select", "round.train", "eval")
INPUT = ("train.batches", "train.h2d", "train.dispatch")


@pytest.fixture(scope="module")
def setup():
    fl = FLConfig(n_clients=8, n_tiers=2, tau=2, rounds=4, seed=0,
                  tier_delay_means=(5.0, 10.0), primary_frac=0.7)
    trainer = CNNTrainer(get_arch("cnn-mnist").reduced(), fl, "mnist",
                         scale=0.01)
    return trainer, fl


def _run(trainer, fl):
    net = WirelessNetwork(fl.n_clients, fl.tier_delay_means, fl.delay_std,
                          fl.mu, fl.failure_delay, fl.seed)
    return run_method("feddct", trainer, net, fl)


def _inside(child, parent) -> bool:
    return (parent["ts_us"] <= child["ts_us"] and child["ts_us"]
            + child["dur_us"] <= parent["ts_us"] + parent["dur_us"])


def test_round_spans_hold_their_phases_in_order(setup):
    trainer, fl = setup
    h_off = _run(trainer, fl)
    with obs.tracing() as tel:
        h_on = _run(trainer, fl)
    for field in ("times", "rounds", "accuracy", "tier", "n_selected",
                  "n_stragglers"):
        assert getattr(h_on, field) == getattr(h_off, field), field

    (size,) = {len(c) for c in trainer.clients}
    rows_per_client = fl.local_epochs * (size // fl.batch_size
                                         ) * fl.batch_size
    rounds = [s for s in tel.spans if s["name"] == "round"]
    assert [s["args"]["rnd"] for s in rounds] == h_on.rounds
    merged = [n - k for n, k in zip(h_on.n_selected, h_on.n_stragglers)]
    assert [s["args"]["survivors"] for s in rounds] == merged
    assert [s["args"]["selected"] for s in rounds] == h_on.n_selected
    assert sum(merged) > 0
    for r, n in zip(rounds, merged):
        inner = [s for s in tel.spans if s is not r and _inside(s, r)]
        names = [s["name"] for s in inner]
        for phase in PHASES:
            assert names.count(phase) == 1, (phase, names)
        assert names.count("round.aggregate") == (1 if n else 0), names
        (train,) = [s for s in inner if s["name"] == "round.train"]
        assert train["args"]["cohort"] == n
        kids = sorted((s for s in inner if s["name"] in INPUT),
                      key=lambda s: s["ts_us"])
        assert [s["name"] for s in kids] == (list(INPUT) if n else [])
        assert all(_inside(s, train) for s in kids)
        for a, b in zip(kids, kids[1:]):
            assert a["ts_us"] + a["dur_us"] <= b["ts_us"]
        if n:
            assert kids[0]["args"]["streams"] == n
            # only the cohort's sample-table rows cross, int32, no labels
            assert kids[1]["args"]["bytes"] == (1 << (n - 1).bit_length()
                                                ) * rows_per_client * 4
    # the round spans cover the virtual clock the history records
    assert rounds[-1]["vt1"] == pytest.approx(h_on.times[-1])


def test_resident_bytes_gauge_recorded_once(setup):
    """The sample table lands on the device once, at the trainer's
    start, as one flat f32 row per sample and int32 labels; a traced
    run records its bytes once and no round records them again."""
    trainer, fl = setup
    with obs.tracing() as tel:
        tr = CNNTrainer(trainer.cfg, fl, "mnist", scale=0.01)
        _run(tr, fl)
    table_x, table_y = tr._resident_tables(None)
    n = sum(len(c) for c in tr.clients)
    assert table_x.shape == (n, tr.clients[0].x[0].size)
    assert (table_x.dtype, table_y.dtype) == (jnp.float32, jnp.int32)
    points = tel.gauge_series["train.resident_bytes"]
    assert [v for _, v in points] == [table_x.nbytes + table_y.nbytes]


def test_spans_land_on_the_profiler_host_plane(setup, tmp_path):
    import jax
    from jax.profiler import ProfileData, TraceAnnotation
    trainer, fl = setup
    _run(trainer, fl)                         # compiled before tracing
    jax.profiler.start_trace(str(tmp_path))
    try:
        t_mark = time.perf_counter()
        with TraceAnnotation("test.clock"):
            pass
        with obs.tracing() as tel:
            _run(trainer, fl)
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(int(ev.start_ns))
    (mark,) = events["test.clock"]
    names = {s["name"] for s in tel.spans}
    assert {"run", "round", *PHASES, *INPUT} <= names
    for name in names:
        got = sorted(events.get(name, []))
        spans = [s for s in tel.spans if s["name"] == name]
        assert len(got) == len(spans), name
        for s in spans:
            want = mark + (tel.t0 + s["ts_us"] / 1e6 - t_mark) * 1e9
            assert min(abs(g - want) for g in got) < 1e6, name
