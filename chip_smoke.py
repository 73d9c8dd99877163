#!/usr/bin/env python3
"""Bring-up smoke of the FedDCT federation server on a TPU.

    python chip_smoke.py             # one chip: the paper's setting
    python chip_smoke.py --chips 4   # the client mesh over four chips

The paper's setting: its MNIST CNN (``cnn-mnist``, 1,630,090 params in
8 leaves) at full width, on the full-size synthetic MNIST set
(``scale=1.0``), 50 clients in 5 tiers, tau=5, with the per-tier delay
means of ``benchmarks/common.py``.  Everything runs through the normal
entry points (``build_fl_clients`` + ``repro.core.run_method``):

  (a) sync ``feddct``, 3 rounds, batched engine, Pallas kernel agg;
  (b) ``feddct_async``, 4 windows, dense client-state store, kernel merge;
  (c) the same async run with the jnp merge, as (b)'s reference;
  plus one window merged both ways on the store (kernel vs jnp).

``--chips 4`` runs only the mesh phase: ``feddct_async`` on
``make_client_mesh(4)`` (sharded cohorts, row-sharded store, per-shard
kernel merge + psum) against the single-device engine.

Each run's ``RunHistory.meta`` must show what ran: the store path,
dense residency, the compiled (not interpreted) kernel, active buffer
donation, and no engine fallback to the per-client loop.  Any failed
check raises and the script exits non-zero.  The last line of stdout
is one JSON object naming the device.  Without a TPU, or outside a
checkout of this repository, it exits non-zero and prints no result.
One process touches JAX; nothing is spawned.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "cnn-mnist"
N_PARAMS = 1_630_090
SYNC_ROUNDS = 3
ASYNC_ROUNDS = 4
LR = 0.003                   # benchmarks/common.py run_fl_experiment
WINDOW_K = 16
# kernel vs jnp on one window: the same convex combination of K+1 f32
# rows, summed in a different order -> a few ulps per element
WINDOW_RTOL, WINDOW_ATOL = 1e-5, 1e-6
# whole runs: ulp-level differences (merge order, sharded training)
# feed later local training (120 Adam steps per client per window),
# which amplifies them window by window.  So final models are held to
# a ratio, their distance from each other over the reference's
# distance from its initial model, and the accuracies to an absolute
# gap.  On a v5e after 4 windows, kernel vs jnp measured 0.067 and
# 0.0146, and the 4-chip mesh vs one chip 0.169 and 0.036; the bounds
# sit above that drift.  They catch a
# merge path that is broken outright (garbage, zeros, lost windows);
# the one-window checks are the tight ones.
RUN_DIVERGENCE = 0.25
RUN_ACC_ATOL = 0.05


def log(msg: str):
    print(msg, flush=True)


def check(ok: bool, what: str):
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class FinalParams:
    """Trainer proxy that keeps the params of the last evaluation — a
    run's final global model (every runner evaluates it at the end)."""

    def __init__(self, trainer):
        self._trainer = trainer
        self.params = None

    def __getattr__(self, name):
        return getattr(self._trainer, name)

    def evaluate(self, params):
        self.params = params
        return self._trainer.evaluate(params)


def paper_config(n_clients: int, seed: int):
    from repro.config.base import FLConfig
    return FLConfig(n_clients=n_clients, n_tiers=5, tau=5, lr=LR,
                    seed=seed, tier_delay_means=(5.0, 10.0, 15.0, 20.0,
                                                 25.0))


def traced_run(method: str, trainer, fl, **kw):
    """One run through ``run_method`` under telemetry (for the compile
    and donation counters) -> (history, final params, wall seconds)."""
    from repro import obs
    from repro.core import run_method
    from repro.fl.network import WirelessNetwork
    net = WirelessNetwork(fl.n_clients, fl.tier_delay_means, fl.delay_std,
                          fl.mu, fl.failure_delay, fl.seed)
    cap = FinalParams(trainer)
    t0 = time.perf_counter()
    with obs.tracing():
        hist = run_method(method, cap, net, fl, **kw)
    wall = time.perf_counter() - t0
    check(cap.params is not None, f"{method}: no final evaluation")
    return hist, cap.params, wall


def compile_seconds(hist) -> float:
    h = hist.meta["telemetry"]["hists"].get("jax.compile_s")
    return h["count"] * h["mean"] if h else 0.0


def check_engine(hist, tag: str, kernel: bool, interpret: bool,
                 mesh_devices: int = 1):
    m = hist.meta
    check(m["engine"] == "batched", f"{tag}: engine {m['engine']}")
    check(m["engine_fallbacks"] == [],
          f"{tag}: engine fell back to the loop: {m['engine_fallbacks']}")
    check(m["kernel_agg"] is kernel, f"{tag}: kernel_agg {m['kernel_agg']}")
    want = interpret if kernel else None
    check(m["kernel_interpret"] is want,
          f"{tag}: kernel_interpret {m['kernel_interpret']} (want {want})")
    check(m["mesh_devices"] == mesh_devices,
          f"{tag}: mesh_devices {m['mesh_devices']}")
    acc = hist.accuracy
    check(len(acc) > 0 and all(0.0 <= a <= 1.0 for a in acc),
          f"{tag}: accuracies {acc}")


def check_store(hist, tag: str):
    m = hist.meta
    check(m["store_path"] == "store", f"{tag}: store_path {m['store_path']}")
    check(m["residency"] == "dense", f"{tag}: residency {m['residency']}")
    tel = m["telemetry"]
    check(tel["counters"].get("store.donation_active", 0) >= 1,
          f"{tag}: store donation not active")
    check(tel["spans"].get("store.merge", {}).get("count", 0) >= 1,
          f"{tag}: no multi-client window merged through the store")


def flat(params):
    import numpy as np

    from repro.kernels.ops import flatten_params_row
    return np.asarray(flatten_params_row(params), np.float64)


def compare_runs(tag: str, got, ref, init, h_got, h_ref):
    import numpy as np
    a, b, p0 = flat(got), flat(ref), flat(init)
    check(a.shape == b.shape == (N_PARAMS,), f"{tag}: param count {a.shape}")
    check(bool(np.isfinite(a).all()), f"{tag}: non-finite params")
    moved = float(np.linalg.norm(b - p0))
    check(moved > 0.0, f"{tag}: the reference never left its init")
    div = float(np.linalg.norm(a - b)) / moved
    same = bool(np.array_equal(a, b))
    dacc = max(abs(x - y) for x, y in zip(h_got.accuracy, h_ref.accuracy))
    log(f"[{tag}] final params |got-ref|/|ref-init| {div!r} "
        f"(tol {RUN_DIVERGENCE}), rel L2 "
        f"{float(np.linalg.norm(a - b) / np.linalg.norm(b))!r}, max abs "
        f"diff {float(np.max(np.abs(a - b)))!r}, max acc diff {dacc!r} "
        f"(tol {RUN_ACC_ATOL}), bit-identical on chip: {same}")
    check(h_got.rounds == h_ref.rounds and h_got.times == h_ref.times,
          f"{tag}: schedules differ")
    check(div <= RUN_DIVERGENCE, f"{tag}: final params diverged {div}")
    check(dacc <= RUN_ACC_ATOL, f"{tag}: accuracy diff {dacc}")


def window_inputs(trainer, fl):
    """One window: WINDOW_K clients trained from the initial model on
    one device, with their staleness-merge coefficients."""
    from repro.core.aggregation import staleness_merge_coefficients
    from repro.core.state import ClientStateStore
    params = trainer.init_params(fl.seed)
    k = min(WINDOW_K, fl.n_clients)
    ids = list(range(k))
    starts = ClientStateStore(params, fl.n_clients).gather(ids)
    stacked, _ = trainer.local_train_cohort(starts, ids,
                                            [977 + c for c in ids])
    alphas = [fl.async_alpha * (s + 1.0) ** -fl.async_a for s in range(k)]
    return params, ids, stacked, staleness_merge_coefficients(alphas)


def merged_row(store, window, use_kernel: bool, tag: str):
    """Merge ``window`` on ``store`` -> the new global row; the store
    must read its write back."""
    import numpy as np
    params, ids, stacked, coef = window
    merged, row = store.merge_scatter(ids, stacked, coef, params,
                                      use_kernel=use_kernel)
    check(np.array_equal(flat(store.gather_one(ids[-1])), flat(merged)),
          f"{tag}: stored row != merged row")
    row = np.asarray(row, np.float64)
    check(bool(np.isfinite(row).all()), f"{tag}: non-finite merged row")
    return row


def compare_rows(tag: str, got, ref):
    import numpy as np
    err = np.abs(got - ref)
    ok = bool(np.all(err <= WINDOW_ATOL + WINDOW_RTOL * np.abs(ref)))
    same = bool(np.array_equal(got, ref))
    log(f"[{tag}] merged row max abs diff {float(err.max())!r} "
        f"(tol rtol={WINDOW_RTOL} atol={WINDOW_ATOL}), "
        f"bit-identical on chip: {same}")
    check(ok, f"{tag}: merged rows differ beyond the window tolerance")


def window_check(trainer, fl):
    """One window merged on the dense store by the fedagg_fold kernel
    and by the jnp merge, from the same trained rows."""
    from repro.core.state import ClientStateStore
    w = window_inputs(trainer, fl)
    rows = [merged_row(ClientStateStore(w[0], fl.n_clients), w, kernel,
                       f"window kernel={kernel}") for kernel in (True, False)]
    compare_rows(f"window K={len(w[1])} kernel vs jnp", *rows)


def mesh_window_check(trainer, fl, mesh):
    """One window merged by the kernel on the row-sharded store (per
    shard + psum) and on the single-device store, same trained rows."""
    from repro.core.state import ClientStateStore
    w = window_inputs(trainer, fl)
    got = merged_row(ClientStateStore(w[0], fl.n_clients, mesh=mesh), w,
                     True, "window mesh")
    ref = merged_row(ClientStateStore(w[0], fl.n_clients), w, True,
                     "window single")
    compare_rows(f"window K={len(w[1])} mesh {mesh.size} vs single", got,
                 ref)


def one_chip(trainer, fl, interpret: bool) -> float:
    """Phases (a), window, (b), (c); returns total compile seconds."""
    total_c = 0.0
    sync_fl = dataclasses.replace(fl, rounds=SYNC_ROUNDS)
    ha, _, wall = traced_run("feddct", trainer, sync_fl, use_kernel_agg=True)
    check_engine(ha, "a:feddct", kernel=True, interpret=interpret)
    check(len(ha.accuracy) == SYNC_ROUNDS, f"a: rounds {ha.rounds}")
    check(ha.meta["telemetry"]["spans"].get("round.aggregate", {})
          .get("count", 0) >= 1, "a: no round aggregated a cohort")
    total_c += compile_seconds(ha)
    log(f"[a] feddct {SYNC_ROUNDS} rounds kernel agg: acc {ha.accuracy} "
        f"wall {wall!r}s compile {compile_seconds(ha)!r}s")

    window_check(trainer, fl)

    async_fl = dataclasses.replace(fl, rounds=ASYNC_ROUNDS)
    out = {}
    for tag, kernel in (("b", True), ("c", False)):
        h, p, wall = traced_run("feddct_async", trainer, async_fl,
                                use_kernel_agg=kernel)
        check_engine(h, f"{tag}:feddct_async", kernel=kernel,
                     interpret=interpret)
        check_store(h, f"{tag}:feddct_async")
        total_c += compile_seconds(h)
        log(f"[{tag}] feddct_async kernel={kernel}: acc {h.accuracy} "
            f"drains {h.meta['n_drains']} mean cohort "
            f"{h.meta['mean_cohort']!r} wall {wall!r}s compile "
            f"{compile_seconds(h)!r}s")
        out[tag] = (h, p)
    compare_runs("b vs c", out["b"][1], out["c"][1],
                 trainer.init_params(fl.seed), out["b"][0], out["c"][0])
    return total_c


def mesh_chips(trainer, fl, n: int, interpret: bool) -> float:
    """feddct_async on an n-device client mesh against the
    single-device engine; returns total compile seconds."""
    from repro.distributed import make_client_mesh
    mesh = make_client_mesh(n)
    mesh_window_check(trainer, fl, mesh)
    async_fl = dataclasses.replace(fl, rounds=ASYNC_ROUNDS)
    total_c = 0.0
    out = {}
    for tag, m in (("mesh", mesh), ("single", None)):
        h, p, wall = traced_run("feddct_async", trainer, async_fl,
                                use_kernel_agg=True, mesh=m)
        check_engine(h, f"{tag}:feddct_async", kernel=True,
                     interpret=interpret,
                     mesh_devices=n if m is not None else 1)
        check_store(h, f"{tag}:feddct_async")
        total_c += compile_seconds(h)
        log(f"[{tag}] feddct_async on {n if m is not None else 1} "
            f"device(s): acc {h.accuracy} drains {h.meta['n_drains']} "
            f"wall {wall!r}s compile {compile_seconds(h)!r}s")
        out[tag] = (h, p)
    compare_runs(f"mesh {n} vs single", out["mesh"][1], out["single"][1],
                 trainer.init_params(fl.seed), out["mesh"][0],
                 out["single"][0])
    return total_c


def smoke(chips: int, *, n_clients: int = 50, scale: float = 1.0,
          seed: int = 0, interpret: bool = False) -> float:
    """Build the federation and run the phases for ``chips``; returns
    the total backend compile seconds.  Raises on any failed check.
    ``interpret`` is the kernel mode the runs must report: False on
    the chip, True only for a CPU rehearsal at a tiny size."""
    from repro.fl.client import build_fl_clients
    fl = paper_config(n_clients, seed)
    t0 = time.perf_counter()
    trainer = build_fl_clients(ARCH, fl, scale=scale)
    log(f"[setup] {ARCH} on {len(trainer.clients)} clients, "
        f"{sum(len(c) for c in trainer.clients)} train samples "
        f"(scale {scale}) in {time.perf_counter() - t0!r}s")
    if chips == 1:
        return one_chip(trainer, fl, interpret)
    return mesh_chips(trainer, fl, chips, interpret)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: the paper setting on one chip; 4: only the "
                         "client-mesh phase over four chips")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    log(f"[device] {dev.device_kind} x {len(devs)} ({dev.platform}), "
        f"jax {jax.__version__}, compile cache {cache}")
    t0 = time.perf_counter()
    compile_s = smoke(args.chips)
    log(f"[done] cold compile seconds {compile_s!r} (backend compiles; "
        f"persistent-cache hits take none), total wall "
        f"{time.perf_counter() - t0!r}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
