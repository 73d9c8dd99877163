"""Shared benchmark helpers: FL experiment runner, timing utilities,
and the machine-readable ``BENCH_<name>.json`` trajectory writer every
A/B harness feeds (so future PRs can diff throughput numbers instead
of re-reading log lines)."""

from __future__ import annotations

import json
import os
import platform
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from repro.config.base import FLConfig
from repro.core import run_method
from repro.fl.client import build_fl_clients
from repro.fl.network import WirelessNetwork
from repro.launch.compile_cache import enable_compile_cache

# every harness imports this module: one compile-cache rule for all
enable_compile_cache()

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")


# ---------------------------------------------------------------------------
# machine-readable benchmark trajectories
# ---------------------------------------------------------------------------

def add_json_arg(ap, name: str):
    """Register ``--json [PATH]`` on an argparse parser: write the
    harness results as ``BENCH_<name>.json`` next to the benchmarks
    (or to an explicit PATH)."""
    ap.add_argument(
        "--json", nargs="?", const="", default=None, metavar="PATH",
        help=f"write machine-readable results (default "
             f"benchmarks/BENCH_{name}.json; pass PATH to override)")


def write_bench_json(name: str, results: Dict, path: Optional[str] = None,
                     extra_context: Optional[Dict] = None) -> str:
    """Dump one benchmark run as ``{"bench", "context", "results"}``.

    ``results`` is the harness's own dict (arms, speedups, gates);
    ``context`` records enough environment to compare trajectories
    across PRs.  ``extra_context`` lets a harness record run-resolved
    facts the argv cannot show — e.g. which snapshot path
    (store/dict) and merge dispatch (kernel/jnp) actually ran — so
    trajectory points stay comparable across PRs that change the
    defaults.  Returns the path written."""
    out = path or os.path.join(os.path.dirname(__file__),
                               f"BENCH_{name}.json")
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    import jax
    context = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "cpu_count": os.cpu_count(),
        "argv": sys.argv[1:],
    }
    context.update(extra_context or {})
    payload = {"bench": name, "context": context, "results": results}
    with open(out, "w") as f:
        json.dump(payload, f, indent=2, default=float)
    print(f"[{name}] json -> {out}")
    return out


def maybe_write_json(args, name: str, results: Dict,
                     extra_context: Optional[Dict] = None):
    """Honor ``add_json_arg``'s flag if the caller passed it."""
    if getattr(args, "json", None) is not None:
        write_bench_json(name, results, path=args.json or None,
                         extra_context=extra_context)


def run_fl_experiment(*, arch: str, method: str, mu: float,
                      primary_frac: float, rounds: int, n_clients: int = 50,
                      tau: int = 5, n_tiers: int = 5, scale: float = 0.05,
                      seed: int = 0, lr: float = 0.003,
                      tier_delay_means=(5.0, 10.0, 15.0, 20.0, 25.0),
                      target_accuracy: float = 0.0, eval_every: int = 1,
                      tag: Optional[str] = None, force: bool = False):
    """Run one (method x setting) cell with caching to results/fl/."""
    tag = tag or (f"{method}_{arch}_mu{mu}_frac{primary_frac}_r{rounds}"
                  f"_c{n_clients}_s{seed}_sc{scale}"
                  f"_d{'-'.join(str(x) for x in tier_delay_means)}")
    os.makedirs(os.path.join(RESULTS_DIR, "fl"), exist_ok=True)
    path = os.path.join(RESULTS_DIR, "fl", tag + ".json")
    if os.path.exists(path) and not force:
        from repro.fl.metrics import RunHistory
        return RunHistory.load(path)
    fl = FLConfig(n_clients=n_clients, n_tiers=n_tiers, tau=tau,
                  rounds=rounds, mu=mu, primary_frac=primary_frac,
                  seed=seed, lr=lr, tier_delay_means=tuple(tier_delay_means),
                  target_accuracy=target_accuracy)
    net = WirelessNetwork(fl.n_clients, fl.tier_delay_means, fl.delay_std,
                          fl.mu, fl.failure_delay, fl.seed)
    trainer = build_fl_clients(arch, fl, scale=scale)
    hist = run_method(method, trainer, net, fl, eval_every=eval_every)
    hist.save(path)
    return hist


def timed_reps(run_once, reps: int) -> Dict:
    """Shared deflaked-arm summary for the A/B harnesses.

    ``run_once()`` -> ``(wall_s, events, extra_dict)`` for one timed
    run.  Returns the BEST rep's numbers (the low-noise headline)
    merged with its extras, plus ``events_per_sec_median`` across reps
    — the smoke-gate statistic (a single descheduled rep on a busy
    2-core CI box can invert a best-of comparison) — and the raw
    ``events_per_sec_samples``.  One definition keeps every harness's
    gate measuring the same statistic."""
    samples: List[float] = []
    best = None
    for _ in range(reps):
        wall, events, extra = run_once()
        eps = events / wall
        samples.append(eps)
        if best is None or eps > best["events_per_sec"]:
            best = {"wall_s": wall, "events": events,
                    "events_per_sec": eps, **extra}
    best["events_per_sec_median"] = float(np.median(samples))
    best["events_per_sec_samples"] = samples
    return best


def traced_run(run_once) -> Dict:
    """One EXTRA telemetry-enabled repetition of a harness arm.

    The timed reps stay untraced (tracing's bookkeeping, however
    cheap, must not perturb the A/B statistic); this runs the arm once
    more under ``repro.obs`` and returns the phase-time breakdown —
    total host seconds per span name — plus the counters and derived
    rates, for the ``BENCH_*.json`` trajectory."""
    from repro import obs
    with obs.tracing() as tel:
        run_once()
    s = tel.summary()
    out = {"phase_s": {name: agg["total_s"]
                       for name, agg in sorted(s["spans"].items())},
           "counters": s["counters"]}
    if "rates" in s:
        out["rates"] = s["rates"]
    return out


def time_fn(fn, *args, iters: int = 20, warmup: int = 3) -> float:
    """Median wall microseconds per call (pre-jitted fns)."""
    import jax
    for _ in range(warmup):
        r = fn(*args)
    jax.block_until_ready(r)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        r = fn(*args)
        jax.block_until_ready(r)
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))
