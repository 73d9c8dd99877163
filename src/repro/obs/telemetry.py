"""Runtime telemetry: span tracer + metrics registry.

FedDCT's claims are about *time* — where a round's wall-clock actually
goes (queue wait vs gather vs cohort train vs merge vs scatter vs
eviction) is the datum every perf PR needs and ``RunHistory`` cannot
carry.  This module is the zero-overhead-when-disabled core:

* ``TEL`` is the module-global active telemetry.  It defaults to the
  ``NOOP`` singleton, whose every method is a constant-return no-op —
  an instrumented call site (``obs.TEL.span(...)``) pays one module
  attribute lookup plus one trivial method call when tracing is off,
  and the no-op ``span`` hands back a shared null context manager (no
  allocation).  ``enable()`` swaps in a recording ``Telemetry``;
  ``disable()`` swaps ``NOOP`` back and returns the recording for
  export.
* ``Telemetry.span(name, **args)`` records BOTH clocks: host
  wall-clock (``perf_counter``) and the simulated virtual time the
  runners maintain via ``set_virtual_time`` — so a trace can show that
  a merge which took 2 ms of host time covered 40 virtual seconds of
  simulated network wait.
* each recording span also opens a ``jax.profiler.TraceAnnotation``
  of its name and start args, so while the JAX profiler runs the
  program's spans land on the trace's host plane, on the same clock
  as the device ops;
* counters / gauges / histograms (``inc`` / ``gauge`` / ``observe``)
  feed the end-of-run aggregate (``summary`` /
  ``summarize_into(hist.meta)`` — the ``meta["telemetry"]`` block).
* jitted-program recompiles are counted for free through
  ``jax.monitoring``: the first ``enable()`` registers listeners that
  increment ``jax.compiles`` (and observe ``jax.compile_s``) on every
  backend compile.  The listeners check ``TEL.enabled`` and stay inert
  when tracing is off.

Clock caveat: JAX dispatch is asynchronous, so a span around a jitted
call measures host-side dispatch plus whatever the wrapped code blocks
on; device time is absorbed by the next blocking point (``evaluate``,
``np.asarray``, ``block_until_ready``).  Spans attribute where the
HOST spends its time — which is exactly the server-step overhead the
store/runtime PRs optimize.

Exporters (JSONL event log, Chrome ``trace_event`` for
chrome://tracing / Perfetto) live in ``repro.obs.export``.
"""

from __future__ import annotations

import contextlib
from time import perf_counter
from typing import Dict, List, Optional

SCHEMA_VERSION = 1

# hard caps so a runaway loop cannot swallow host memory; overflow is
# counted (``telemetry.dropped_*``), never silent
MAX_SPANS = 500_000
MAX_SERIES = 100_000
MAX_HIST = 500_000


class _NoopSpan:
    """Shared null span: context manager AND manual start/end, every
    method a no-op returning ``self`` so call sites never branch."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def start(self):
        return self

    def end(self):
        return self

    def set(self, **args):
        return self


_NOOP_SPAN = _NoopSpan()


class NoopTelemetry:
    """The disabled-mode singleton: every hook is a constant no-op."""

    __slots__ = ()
    enabled = False

    def span(self, name, **args):
        return _NOOP_SPAN

    def inc(self, name, n=1):
        pass

    def gauge(self, name, value):
        pass

    def observe(self, name, value):
        pass

    def set_virtual_time(self, t):
        pass

    def summarize_into(self, meta):
        pass


NOOP = NoopTelemetry()

# the active telemetry — instrumented modules read ``obs.TEL`` fresh on
# every use (one attribute lookup), so enable/disable swaps take effect
# everywhere at once
TEL = NOOP


class Span:
    """One traced section: wall-clock + virtual-time interval with
    attached args.  Works as a context manager or via explicit
    ``start()`` / ``end()`` (for loops that cannot re-indent).

    Each span also opens a ``jax.profiler.TraceAnnotation`` of its name
    and start args, so it lands on the profiler's host plane on the
    device trace's clock (a no-op unless the profiler is running)."""

    __slots__ = ("_tel", "name", "args", "t0", "vt0", "_ann")

    def __init__(self, tel: "Telemetry", name: str, args: Dict):
        self._tel = tel
        self.name = name
        self.args = args
        self.t0 = 0.0
        self.vt0 = 0.0
        self._ann = None

    def set(self, **args):
        self.args.update(args)
        return self

    def start(self):
        self.t0 = perf_counter()
        self.vt0 = self._tel.vt
        from jax.profiler import TraceAnnotation
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        return self

    def end(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self._tel._record_span(self)
        return self

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.end()
        return False


class Telemetry:
    """Recording telemetry: spans + counters + gauges + histograms."""

    enabled = True

    def __init__(self):
        self.t0 = perf_counter()     # trace epoch (host clock origin)
        self.vt = 0.0                # current simulated virtual time
        self.spans: List[Dict] = []
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.gauge_series: Dict[str, List] = {}
        self.hists: Dict[str, List[float]] = {}

    # -- spans ----------------------------------------------------------
    def span(self, name: str, **args) -> Span:
        return Span(self, name, args)

    def _record_span(self, s: Span):
        if len(self.spans) >= MAX_SPANS:
            self.inc("telemetry.dropped_spans")
            return
        now = perf_counter()
        self.spans.append({
            "name": s.name,
            "ts_us": (s.t0 - self.t0) * 1e6,
            "dur_us": (now - s.t0) * 1e6,
            "vt0": s.vt0,
            "vt1": self.vt,
            "args": s.args,
        })

    # -- virtual clock --------------------------------------------------
    def set_virtual_time(self, t: float):
        self.vt = float(t)

    # -- metrics --------------------------------------------------------
    def inc(self, name: str, n=1):
        self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name: str, value):
        value = float(value)
        self.gauges[name] = value
        series = self.gauge_series.setdefault(name, [])
        if len(series) < MAX_SERIES:
            series.append(((perf_counter() - self.t0) * 1e6, value))
        else:
            self.inc("telemetry.dropped_gauge_points")

    def observe(self, name: str, value):
        """``value`` may be a device scalar: it is kept as it is, with
        no host sync, and read back in ``summary``."""
        vals = self.hists.setdefault(name, [])
        if len(vals) < MAX_HIST:
            vals.append(value if hasattr(value, "block_until_ready")
                        else float(value))
        else:
            self.inc("telemetry.dropped_hist_points")

    def _read_back_hists(self):
        """Replace every device scalar ``observe`` kept by its float,
        in one batched read."""
        lazy = [(vals, i) for vals in self.hists.values()
                for i, v in enumerate(vals) if type(v) is not float]
        if not lazy:
            return
        import jax
        got = jax.device_get([vals[i] for vals, i in lazy])
        for (vals, i), v in zip(lazy, got):
            vals[i] = float(v)

    # -- aggregate summary ----------------------------------------------
    def summary(self) -> Dict:
        """End-of-run aggregate: per-span totals, counters, last gauge
        values, histogram stats, and derived rates (prefetch hit rate,
        lookahead accuracy) when their counters exist."""
        spans: Dict[str, Dict] = {}
        for s in self.spans:
            agg = spans.setdefault(s["name"], {"count": 0, "total_s": 0.0,
                                               "total_vt": 0.0})
            agg["count"] += 1
            agg["total_s"] += s["dur_us"] / 1e6
            agg["total_vt"] += s["vt1"] - s["vt0"]
        for agg in spans.values():
            agg["mean_s"] = agg["total_s"] / agg["count"]
        self._read_back_hists()
        hists = {}
        for name, vals in self.hists.items():
            import numpy as np
            a = np.asarray(vals, np.float64)
            hists[name] = {"count": int(a.size), "mean": float(a.mean()),
                           "p50": float(np.percentile(a, 50)),
                           "p95": float(np.percentile(a, 95)),
                           "max": float(a.max())}
        out = {"wall_s": perf_counter() - self.t0,
               "spans": spans,
               "counters": dict(self.counters),
               "gauges": dict(self.gauges),
               "hists": hists}
        rates = {}
        c = self.counters
        hit = c.get("residency.demand_hit", 0)
        miss = c.get("residency.demand_promote", 0)
        if hit + miss:
            rates["prefetch_hit_rate"] = hit / (hit + miss)
        la_hit = c.get("lookahead.hit", 0)
        la_miss = c.get("lookahead.miss", 0)
        if la_hit + la_miss:
            rates["lookahead_accuracy"] = la_hit / (la_hit + la_miss)
        if rates:
            out["rates"] = rates
        return out

    def summarize_into(self, meta: Dict):
        """Fold the aggregate into a ``RunHistory.meta`` dict (the
        ``meta["telemetry"]`` block every traced run carries)."""
        meta["telemetry"] = self.summary()

    # -- export convenience (see repro.obs.export) ----------------------
    def export_jsonl(self, path: str) -> str:
        from repro.obs.export import export_jsonl
        return export_jsonl(self, path)

    def export_chrome(self, path: str) -> str:
        from repro.obs.export import export_chrome
        return export_chrome(self, path)


# -- enable / disable ----------------------------------------------------

_jax_hooked = False


def _hook_jax_monitoring():
    """Count jitted-program recompiles through ``jax.monitoring``.

    Registered once per process (listeners cannot be unregistered
    individually without clobbering other callers'); the callbacks read
    the CURRENT ``TEL`` and are inert when tracing is off."""
    global _jax_hooked
    if _jax_hooked:
        return
    try:
        from jax import monitoring
    except ImportError:                                    # pragma: no cover
        return

    def _on_duration(event, duration, **kw):
        t = TEL
        if t.enabled and event.endswith("backend_compile_duration"):
            t.inc("jax.compiles")
            t.observe("jax.compile_s", duration)

    def _on_event(event, **kw):
        t = TEL
        if t.enabled and "compilation_cache" in event:
            t.inc("jax.cache." + event.rsplit("/", 1)[-1])

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _jax_hooked = True


def enable(tel: Optional[Telemetry] = None) -> Telemetry:
    """Install a recording telemetry as the process-wide ``TEL``."""
    global TEL
    _hook_jax_monitoring()
    TEL = tel if tel is not None else Telemetry()
    return TEL


def disable() -> "Telemetry | NoopTelemetry":
    """Swap ``NOOP`` back in; returns the telemetry that was active
    (export it, then drop it)."""
    global TEL
    t = TEL
    TEL = NOOP
    return t


@contextlib.contextmanager
def tracing(tel: Optional[Telemetry] = None):
    """``with tracing() as tel:`` — enable for the block, always
    restore ``NOOP`` after."""
    t = enable(tel)
    try:
        yield t
    finally:
        disable()
