"""Telemetry subsystem (repro.obs): zero-overhead disabled path,
span/counter recording, exporters + validator, and the PR's acceptance
gates — traced runs are numerically invisible (bit-identical
histories) and a traced tiered feddct_async run produces a trace whose
spans cover >= 95% of the measured wall-clock with per-window
gather/train/merge/scatter attribution and residency counters."""

from __future__ import annotations

import json
import time

import pytest

from repro import obs
from repro.config.base import FLConfig
from repro.core import run_method
from repro.core.tiering import tiering
from repro.fl.network import WirelessNetwork
from repro.fl.testing import SyntheticCohortTrainer
from repro.obs import flstats
from repro.obs import report as obs_report
from repro.obs import telemetry as obs_tel
from repro.obs.validate import (sniff_format, validate_chrome,
                                validate_chrome_file, validate_file,
                                validate_lines)


def _net(fl):
    return WirelessNetwork(fl.n_clients, fl.tier_delay_means, fl.delay_std,
                           fl.mu, fl.failure_delay, fl.seed)


def _fl(**kw):
    kw.setdefault("n_clients", 8)
    kw.setdefault("n_tiers", 4)
    kw.setdefault("tau", 2)
    kw.setdefault("rounds", 3)
    kw.setdefault("seed", 0)
    return FLConfig(**kw)


# ---------------------------------------------------------------------------
# core: disabled default, span recording, metrics
# ---------------------------------------------------------------------------

def test_noop_default_and_restore():
    assert obs_tel.TEL is obs_tel.NOOP
    assert not obs_tel.TEL.enabled
    with obs.tracing() as tel:
        assert obs_tel.TEL is tel
        assert tel.enabled
    assert obs_tel.TEL is obs_tel.NOOP


def test_noop_span_is_shared_and_inert():
    s1 = obs_tel.NOOP.span("a", x=1)
    s2 = obs_tel.NOOP.span("b")
    assert s1 is s2                       # no per-call allocation
    with s1:
        pass
    s1.start().set(y=2).end()             # manual API is also a no-op
    obs_tel.NOOP.inc("c")
    obs_tel.NOOP.gauge("g", 1.0)
    obs_tel.NOOP.observe("h", 1.0)
    obs_tel.NOOP.set_virtual_time(5.0)
    meta = {}
    obs_tel.NOOP.summarize_into(meta)
    assert meta == {}                     # disabled runs never touch meta


def test_disabled_overhead_under_noise_floor():
    """The disabled hot-path cost (attribute lookup + no-op span) must
    sit at sub-microsecond scale — compare against an empty loop."""
    n = 50_000

    def bare():
        t0 = time.perf_counter()
        for _ in range(n):
            pass
        return time.perf_counter() - t0

    def instrumented():
        t0 = time.perf_counter()
        for _ in range(n):
            with obs_tel.TEL.span("x"):
                pass
        return time.perf_counter() - t0

    bare_s = min(bare() for _ in range(3))
    inst_s = min(instrumented() for _ in range(3))
    per_call_us = (inst_s - bare_s) / n * 1e6
    assert per_call_us < 10.0, f"disabled span costs {per_call_us:.2f}us"


def test_span_records_wall_and_virtual_time():
    with obs.tracing() as tel:
        tel.set_virtual_time(10.0)
        with tel.span("work", rows=4):
            time.sleep(0.01)
            tel.set_virtual_time(25.0)
    (s,) = tel.spans
    assert s["name"] == "work"
    assert s["args"] == {"rows": 4}
    assert s["dur_us"] >= 10_000          # slept 10 ms of host time
    assert s["vt0"] == 10.0 and s["vt1"] == 25.0


def test_manual_span_and_metrics_summary():
    with obs.tracing() as tel:
        sp = tel.span("phase", k=1).start()
        tel.inc("hits")
        tel.inc("hits", 2)
        tel.gauge("depth", 3)
        tel.gauge("depth", 7)
        for v in (1.0, 2.0, 3.0, 4.0):
            tel.observe("cohort.size", v)
        sp.end()
        tel.inc("lookahead.hit", 3)
        tel.inc("lookahead.miss", 1)
    s = tel.summary()
    assert s["spans"]["phase"]["count"] == 1
    assert s["counters"]["hits"] == 3
    assert s["gauges"]["depth"] == 7.0
    h = s["hists"]["cohort.size"]
    assert h["count"] == 4 and h["mean"] == 2.5 and h["max"] == 4.0
    assert s["rates"]["lookahead_accuracy"] == 0.75
    meta = {}
    tel.summarize_into(meta)
    assert meta["telemetry"]["counters"]["hits"] == 3


def test_span_cap_counts_drops():
    with obs.tracing() as tel:
        old = obs_tel.MAX_SPANS
        obs_tel.MAX_SPANS = 2
        try:
            for _ in range(5):
                with tel.span("x"):
                    pass
        finally:
            obs_tel.MAX_SPANS = old
    assert len(tel.spans) == 2
    assert tel.counters["telemetry.dropped_spans"] == 3


# ---------------------------------------------------------------------------
# exporters + validator
# ---------------------------------------------------------------------------

def _tiny_trace():
    with obs.tracing() as tel:
        tel.set_virtual_time(1.0)
        with tel.span("run", method="t"):
            with tel.span("window.merge", cohort=2):
                pass
        tel.inc("drain.count")
        tel.gauge("queue.depth", 5)
        tel.observe("cohort.size", 2)
    return tel


def test_jsonl_export_validates(tmp_path):
    tel = _tiny_trace()
    p = str(tmp_path / "t.jsonl")
    assert tel.export_jsonl(p) == p
    errors, counts = validate_file(p)
    assert errors == []
    assert counts["meta"] == 1 and counts["summary"] == 1
    assert counts["span"] == 2
    with open(p) as f:
        first = json.loads(f.readline())
    assert first["type"] == "meta"
    assert first["schema_version"] == obs.SCHEMA_VERSION


def test_validator_rejects_corrupt_traces():
    errors, _ = validate_lines(["not json at all"])
    assert any("not JSON" in e for e in errors)
    meta = json.dumps({"type": "meta",
                       "schema_version": obs.SCHEMA_VERSION,
                       "clock": "perf_counter_us"})
    span = json.dumps({"type": "span", "name": "x", "ts_us": 0.0,
                       "dur_us": 1.0, "vt0": 0, "vt1": 0, "args": {}})
    summ = json.dumps({"type": "summary", "wall_s": 0.1, "spans": {},
                       "counters": {}})
    # happy path
    assert validate_lines([meta, span, summ])[0] == []
    # meta not first
    assert validate_lines([span, meta, summ])[0]
    # missing required span key
    bad = json.dumps({"type": "span", "name": "x"})
    assert any("missing" in e for e in validate_lines([meta, bad, summ])[0])
    # unknown record type
    unk = json.dumps({"type": "mystery"})
    assert any("unknown" in e for e in validate_lines([meta, span, unk,
                                                       summ])[0])
    # wrong schema version
    old = json.dumps({"type": "meta", "schema_version": 99,
                      "clock": "perf_counter_us"})
    assert any("schema_version" in e
               for e in validate_lines([old, span, summ])[0])


def test_chrome_export_shape(tmp_path):
    tel = _tiny_trace()
    p = str(tmp_path / "t.json")
    tel.export_chrome(p)
    doc = json.load(open(p))
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"run", "window.merge"}
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert "vt0" in e["args"] and "vt1" in e["args"]
    assert any(e["ph"] == "C" and e["name"] == "queue.depth"
               for e in events)
    assert doc["otherData"]["schema_version"] == obs.SCHEMA_VERSION
    assert doc["otherData"]["counters"]["drain.count"] == 1


# ---------------------------------------------------------------------------
# numerical invisibility: tracing must not change any history
# ---------------------------------------------------------------------------

CASES = [
    ("fedasync", dict(window=3, eval_every=2), None),
    ("fedbuff", dict(eval_every=2), None),
    ("feddct_async", dict(), None),
    ("feddct_async", dict(), 2),
    ("feddct", dict(), None),
    ("tifl", dict(), None),
]


@pytest.mark.parametrize("method,kw,capacity", CASES,
                         ids=["fedasync-window", "fedbuff",
                              "feddct_async-dense", "feddct_async-tiered",
                              "feddct-sync", "tifl-sync"])
def test_tracing_is_numerically_invisible(method, kw, capacity):
    """Bit-identical RunHistories with tracing on vs off; the traced
    meta differs ONLY by the additive ``telemetry`` block."""
    fl = _fl()
    if capacity is not None:
        kw = dict(kw, store_capacity=capacity)
    h_off = run_method(method, SyntheticCohortTrainer(), _net(fl), fl, **kw)
    with obs.tracing():
        h_on = run_method(method, SyntheticCohortTrainer(), _net(fl), fl,
                          **kw)
    assert h_on.times == h_off.times
    assert h_on.rounds == h_off.rounds
    assert h_on.accuracy == h_off.accuracy
    assert h_on.tier == h_off.tier
    assert h_on.n_selected == h_off.n_selected
    assert "telemetry" not in h_off.meta
    on_meta = dict(h_on.meta)
    assert on_meta.pop("telemetry") is not None
    assert on_meta == h_off.meta


def test_sync_loops_record_uniform_execution_meta():
    """Satellite: every sync loop records the resolved kernel/mesh
    facts the async runners already carry."""
    fl = _fl(rounds=2)
    for method in ("feddct", "fedavg", "tifl", "fedprox"):
        h = run_method(method, SyntheticCohortTrainer(), _net(fl), fl)
        assert h.meta["kernel_agg"] is False, method
        assert h.meta["mesh_devices"] == 1, method


def test_sync_loop_traced_summary():
    fl = _fl(rounds=2)
    with obs.tracing():
        h = run_method("feddct", SyntheticCohortTrainer(), _net(fl), fl)
    t = h.meta["telemetry"]
    assert t["spans"]["run"]["count"] == 1
    assert "round.train" in t["spans"]
    assert "round.select" in t["spans"]
    # virtual clock advanced: the run span covers simulated time
    assert t["spans"]["run"]["total_vt"] > 0


# ---------------------------------------------------------------------------
# acceptance: traced tiered feddct_async end-to-end
# ---------------------------------------------------------------------------

def test_traced_tiered_feddct_async_acceptance(tmp_path):
    """The PR acceptance gate: a tiered-residency feddct_async run
    under ``--trace`` yields (a) spans covering >= 95% of the measured
    run wall-clock, (b) per-window gather/train/merge/scatter and
    eviction attribution, (c) residency + prefetch counters, (d) a
    Chrome trace and a JSONL trace that validates."""
    fl = _fl(rounds=4)
    t0 = time.perf_counter()
    with obs.tracing() as tel:
        hist = run_method("feddct_async", SyntheticCohortTrainer(),
                          _net(fl), fl, store_capacity=4)
    wall = time.perf_counter() - t0
    t = hist.meta["telemetry"]

    # (a) coverage: the "run" span tracks the whole measured call
    run_s = t["spans"]["run"]["total_s"]
    assert run_s >= 0.95 * wall, f"run span {run_s:.4f}s < 95% of {wall:.4f}s"

    # (b) per-window phase attribution exists
    for name in ("window.prefetch", "window.merge", "window.gather",
                 "window.train", "store.merge", "store.scatter",
                 "round.select", "eval"):
        assert name in t["spans"], f"missing span {name}"

    # (c) residency + lookahead counters (capacity 4 with tau=2 windows:
    # demand staging and prefetch both fire)
    counters = t["counters"]
    assert any(k.startswith("residency.") for k in counters), counters
    assert counters.get("lookahead.hit", 0) > 0
    assert "lookahead_accuracy" in t.get("rates", {})
    assert "drain.deadline" in counters or "drain.budget" in counters

    # (d) both exporters produce valid artifacts
    jp = tel.export_jsonl(str(tmp_path / "t.jsonl"))
    errors, counts = validate_file(jp)
    assert errors == []
    assert counts["span"] == len(tel.spans)
    cp = tel.export_chrome(str(tmp_path / "t.json"))
    doc = json.load(open(cp))
    assert any(e.get("name") == "run" for e in doc["traceEvents"])


def test_prefetch_hit_rate_surfaces_when_windows_fit():
    """With a hot tier at least as wide as the windows, gathers take
    the demand-staging path and the prefetch hit rate is defined."""
    fl = _fl(n_clients=6, rounds=4)
    with obs.tracing():
        h = run_method("fedasync", SyntheticCohortTrainer(), _net(fl), fl,
                       window=2, store_capacity=4, eval_every=2)
    t = h.meta["telemetry"]
    c = t["counters"]
    demand = (c.get("residency.demand_hit", 0)
              + c.get("residency.demand_promote", 0))
    assert demand > 0, c
    assert "prefetch_hit_rate" in t["rates"]
    assert 0.0 <= t["rates"]["prefetch_hit_rate"] <= 1.0


# ---------------------------------------------------------------------------
# flstats: labeled FL-semantic streams
# ---------------------------------------------------------------------------

def test_label_roundtrip():
    assert flstats.label("fl.tier.size") == "fl.tier.size"
    name = flstats.label("fl.tier.migration", to=2, **{"from": 1})
    assert name == "fl.tier.migration{from=1,to=2}"   # sorted keys
    base, labels = flstats.parse_label(name)
    assert base == "fl.tier.migration"
    assert labels == {"from": "1", "to": "2"}
    assert flstats.parse_label("plain.counter") == ("plain.counter", {})


def test_flstats_disabled_is_inert():
    """Every record_* early-returns on the NOOP singleton (which has
    __slots__, so any state leak would raise)."""
    assert obs_tel.TEL is obs_tel.NOOP
    flstats.record_tiering([[0, 1]], thresholds=[1.0], population=2)
    flstats.record_selection([(0, 0), 1])
    flstats.record_response(1, 1.0, 2.0, timed_out=False)
    flstats.record_staleness([1, 2], [1, None])
    flstats.record_straggler("dropped", tier=1)
    flstats.record_client_updates([0, 1])
    flstats.record_update_norm(None, 0)


def test_update_norm_is_one_device_scalar_until_summary():
    """While tracing, the cohort update norm stays a device scalar (no
    host read per window) and ``summary`` reads it back as the L2 norm
    of the first ``n_rows`` rows."""
    import jax.numpy as jnp
    import numpy as np
    stacked = {"a": jnp.arange(12.0).reshape(4, 3),
               "b": jnp.ones((4, 2, 2), jnp.bfloat16)}
    with obs.tracing() as tel:
        flstats.record_update_norm(stacked, 3)
        flstats.record_update_norm(stacked, 4)
        kept = tel.hists["fl.cohort.update_norm"]
        assert [type(v) is float for v in kept] == [False, False]
        assert all(v.shape == () for v in kept)
        summary = tel.summary()
    want = [np.sqrt(np.sum(np.arange(9.0) ** 2) + 12),
            np.sqrt(np.sum(np.arange(12.0) ** 2) + 16)]
    assert tel.hists["fl.cohort.update_norm"] == pytest.approx(want)
    assert all(type(v) is float for v in tel.hists["fl.cohort.update_norm"])
    assert summary["hists"]["fl.cohort.update_norm"]["max"] == pytest.approx(
        want[1])


def test_flstats_cardinality_cap(monkeypatch):
    monkeypatch.setattr(flstats, "MAX_LABELS_PER_METRIC", 2)
    with obs.tracing() as tel:
        for t in range(5):
            flstats.record_response(t + 1, 1.0, 2.0, timed_out=False)
    admitted = [k for k in tel.hists if k.startswith("fl.response_s{")]
    assert len(admitted) == 2
    assert tel.counters[flstats.DROPPED] > 0
    # a fresh tracing block starts a fresh label budget
    with obs.tracing() as tel2:
        flstats.record_response(9, 1.0, 2.0, timed_out=False)
    assert "fl.response_s{tier=9}" in tel2.hists
    assert flstats.DROPPED not in tel2.counters


def test_flstats_migration_matrix_seeded_drift():
    """Satellite gate: a deterministic drifting-response scenario
    produces the hand-checked migration-matrix entries and per-tier
    threshold series (client 0 then client 1 slow down and sink from
    tier 1 to tier 2, displacing the fast ones upward)."""
    from repro.core.selection import tier_timeouts
    ats = [
        {0: 1.0, 1: 2.0, 2: 3.0, 3: 4.0},   # [[0,1],[2,3]]
        {0: 5.0, 1: 2.0, 2: 3.0, 3: 4.0},   # [[1,2],[3,0]]
        {0: 5.0, 1: 6.0, 2: 3.0, 3: 4.0},   # [[2,3],[0,1]]
    ]
    with obs.tracing() as tel:
        for at in ats:
            tiers = tiering(at, 2)
            flstats.record_tiering(
                tiers, thresholds=tier_timeouts(tiers, at, beta=2.0,
                                                omega=100.0),
                population=4)
    c = tel.counters
    assert c["fl.tier.migration{from=1,to=2}"] == 2
    assert c["fl.tier.migration{from=2,to=1}"] == 2
    assert c["fl.tier.rounds"] == 3
    assert tel.gauges["fl.population"] == 4.0
    # membership + threshold series: one point per round per tier
    for t in (1, 2):
        assert len(tel.gauge_series[f"fl.tier.size{{tier={t}}}"]) == 3
        assert len(tel.gauge_series[f"fl.tier.threshold_s{{tier={t}}}"]) == 3
    # Eq. 7 thresholds (beta * tier mean): hand-computed series
    assert tel.hists["fl.threshold_s{tier=1}"] == [3.0, 5.0, 7.0]
    assert tel.hists["fl.threshold_s{tier=2}"] == [7.0, 9.0, 11.0]


def test_flstats_response_and_straggler_streams():
    with obs.tracing() as tel:
        flstats.record_response(1, 3.0, 4.0, timed_out=False)
        flstats.record_response(1, 5.0, 4.0, timed_out=True)
        flstats.record_response(2, 8.0, 10.0, timed_out=False)
        flstats.record_straggler("dropped", tier=1)
        flstats.record_straggler("carried", tier=2, n=2)
        flstats.record_staleness([0, 3], [1, 2])
        flstats.record_selection([(4, 0), (5, 1), 6], population=8)
        flstats.record_client_updates([4, 5])
    c = tel.counters
    assert c["fl.tier.participate{tier=1}"] == 1
    assert c["fl.tier.timeout{tier=1}"] == 1
    assert c["fl.tier.participate{tier=2}"] == 1
    assert c["fl.straggler.dropped{tier=1}"] == 1
    assert c["fl.straggler.carried{tier=2}"] == 2
    assert c["fl.tier.selected{tier=1}"] == 1
    assert c["fl.tier.selected{tier=2}"] == 1
    assert c["fl.client.selected{client=6}"] == 1
    assert c["fl.client.update{client=4}"] == 1
    assert tel.hists["fl.response_s{tier=1}"] == [3.0, 5.0]
    assert tel.hists["fl.response_frac{tier=1}"] == [0.75, 1.25]
    assert tel.hists["fl.staleness"] == [0.0, 3.0]
    assert tel.hists["fl.staleness{tier=2}"] == [3.0]
    assert tel.gauges["fl.population"] == 8.0


# ---------------------------------------------------------------------------
# report: per-tier run report from traces / histories
# ---------------------------------------------------------------------------

def _traced_async_run(fl=None, **kw):
    fl = fl or _fl(rounds=4)
    with obs.tracing() as tel:
        hist = run_method("feddct_async", SyntheticCohortTrainer(),
                          _net(fl), fl, **kw)
    return fl, tel, hist


def test_flstats_report_acceptance_feddct_async():
    """Acceptance gate: a traced tiered feddct_async run yields a
    report with per-tier participation counts, timeout-hit rates, and
    the migration matrix, all consistent with the raw counters."""
    fl, tel, hist = _traced_async_run(store_capacity=4)
    t = hist.meta["telemetry"]
    c = t["counters"]
    rep = obs_report.build_report(t, hist.to_json())

    assert rep["rounds"] == c["fl.tier.rounds"] > 0
    assert rep["population"] == fl.n_clients
    assert rep["tiers"], "per-tier table is empty"
    for tier, row in rep["tiers"].items():
        assert row["selected"] == c.get(f"fl.tier.selected{{tier={tier}}}",
                                        0)
        seen = row["participated"] + row["timeout_hits"]
        if seen:
            assert row["timeout_hit_rate"] == pytest.approx(
                row["timeout_hits"] / seen)
        if "mean_response_s" in row:
            assert row["mean_response_s"] > 0
    total_sel = sum(r["selected"] for r in rep["tiers"].values())
    client_sel = sum(v for k, v in c.items()
                     if k.startswith("fl.client.selected{"))
    assert total_sel == client_sel > 0
    # migration matrix mirrors the labeled counters
    mig = sum(v for k, v in c.items()
              if k.startswith("fl.tier.migration{"))
    assert rep["n_migrations"] == mig
    # fairness over the whole fleet
    f = rep["fairness"]["selection"]
    assert f["population"] == fl.n_clients
    assert 0.0 <= f["gini"] <= 1.0
    assert 0.0 < f["coverage"] <= 1.0
    # staleness + cohort update norms flowed through
    assert "fl.staleness" in t["hists"]
    assert "cohort_update_norm" in rep
    # trajectory came from the history
    assert rep["trajectory"]["evals"] == len(hist.accuracy)
    # the text rendering mentions every tier row
    text = obs_report.format_report(rep, source="test")
    for tier in rep["tiers"]:
        assert f"\n{tier:>4}  " in text or str(tier) in text


def test_report_sources_agree(tmp_path):
    """The three report sources (JSONL trace, chrome trace, RunHistory
    JSON) produce the same per-tier table."""
    _, tel, hist = _traced_async_run()
    jp = str(tmp_path / "t.jsonl")
    cp = str(tmp_path / "t.json")
    hp = str(tmp_path / "h.json")
    tel.export_jsonl(jp)
    tel.export_chrome(cp)
    hist.save(hp)
    reports = []
    for p in (jp, cp, hp):
        summary, history = obs_report.load_source(p)
        assert summary is not None, p
        reports.append(obs_report.build_report(summary, history))
    assert reports[0]["tiers"] == reports[1]["tiers"] == reports[2]["tiers"]
    assert (reports[0]["migration_matrix"]
            == reports[1]["migration_matrix"]
            == reports[2]["migration_matrix"])
    # only the history source carries the trajectory
    assert "trajectory" not in reports[0]
    assert reports[2]["trajectory"]["evals"] == len(hist.accuracy)


def test_report_cli(tmp_path, capsys):
    _, tel, hist = _traced_async_run()
    jp = str(tmp_path / "t.jsonl")
    tel.export_jsonl(jp)
    out_json = str(tmp_path / "rep.json")
    assert obs_report.main([jp, "--json", out_json]) == 0
    text = capsys.readouterr().out
    assert "FL run report" in text
    rep = json.load(open(out_json))
    assert rep["tiers"]
    # an untraced input is a clean exit-2 diagnostic, not a crash
    hp = str(tmp_path / "h.json")
    hist.meta.pop("telemetry")
    hist.save(hp)
    assert obs_report.main([hp]) == 2
    bogus = str(tmp_path / "x.json")
    with open(bogus, "w") as f:
        f.write("{not json")
    assert obs_report.main([bogus]) == 2


# ---------------------------------------------------------------------------
# trace-format parity + chrome validation
# ---------------------------------------------------------------------------

def test_trace_format_parity(tmp_path):
    """Satellite gate: the end-of-run aggregate folded into
    ``RunHistory.meta["telemetry"]`` is identical to what BOTH export
    formats embed (only ``wall_s`` differs — it is stamped at export
    time)."""
    _, tel, hist = _traced_async_run()
    jp = str(tmp_path / "t.jsonl")
    cp = str(tmp_path / "t.json")
    tel.export_jsonl(jp)
    tel.export_chrome(cp)
    with open(jp) as f:
        jsonl_summary = [json.loads(l) for l in f if l.strip()][-1]
    assert jsonl_summary.pop("type") == "summary"
    chrome_summary = json.load(open(cp))["otherData"]["summary"]
    meta_summary = hist.meta["telemetry"]
    for key in ("spans", "counters", "gauges", "hists"):
        assert jsonl_summary[key] == meta_summary[key], key
        assert chrome_summary[key] == meta_summary[key], key
    assert jsonl_summary.get("rates") == meta_summary.get("rates") \
        == chrome_summary.get("rates")


def test_chrome_validator(tmp_path):
    tel = _tiny_trace()
    p = str(tmp_path / "t.json")
    tel.export_chrome(p)
    errors, counts = validate_chrome_file(p)
    assert errors == []
    assert counts["X"] == 2 and counts["M"] == 2
    assert sniff_format(p) == "chrome"
    jp = str(tmp_path / "t.jsonl")
    tel.export_jsonl(jp)
    assert sniff_format(jp) == "jsonl"


def test_chrome_validator_rejects_corrupt():
    assert validate_chrome([])[0]                       # not an object
    assert any("traceEvents" in e for e in validate_chrome({})[0])
    ok = {"traceEvents": [
        {"name": "x", "ph": "X", "pid": 0, "tid": 0, "ts": 0.0,
         "dur": 1.0, "args": {"vt0": 0.0, "vt1": 0.0}}],
        "otherData": {"schema_version": obs.SCHEMA_VERSION,
                      "counters": {},
                      "summary": {"wall_s": 0.1, "spans": {},
                                  "counters": {}}}}
    assert validate_chrome(ok)[0] == []
    # X span without the virtual-time interval
    bad = json.loads(json.dumps(ok))
    bad["traceEvents"][0]["args"] = {}
    assert any("vt0" in e for e in validate_chrome(bad)[0])
    # wrong schema version
    bad = json.loads(json.dumps(ok))
    bad["otherData"]["schema_version"] = 99
    assert any("schema_version" in e for e in validate_chrome(bad)[0])
    # no spans at all
    bad = json.loads(json.dumps(ok))
    bad["traceEvents"] = []
    assert any("no spans" in e for e in validate_chrome(bad)[0])
    # summary missing required keys
    bad = json.loads(json.dumps(ok))
    bad["otherData"]["summary"] = {}
    assert any("summary missing" in e for e in validate_chrome(bad)[0])
