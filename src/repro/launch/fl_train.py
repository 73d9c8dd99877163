"""FedDCT over any assigned architecture — the paper's scheduler driving
LM clients (the datacenter embodiment from DESIGN.md §2).

    PYTHONPATH=src python -m repro.launch.fl_train --arch llama3.2-1b \
        --method feddct --rounds 20 --clients 10 --mu 0.2

Each FL client's local step is the same train_step the dry-run lowers;
on CPU the reduced config is used so rounds are fast.  The wireless
delay/failure model supplies virtual time exactly as for the CNN runs.
"""

from __future__ import annotations

import argparse

from repro.config.base import FLConfig
from repro.core import run_method
from repro.fl.client import build_fl_clients
from repro.fl.network import WirelessNetwork
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--method", default="feddct",
                    choices=["feddct", "fedavg", "tifl", "fedasync",
                             "fedprox", "fedbuff", "feddct_async"])
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--tiers", type=int, default=5)
    ap.add_argument("--tau", type=int, default=2)
    ap.add_argument("--mu", type=float, default=0.0)
    ap.add_argument("--primary-frac", type=float, default=0.7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", default="batched",
                    choices=["batched", "looped"],
                    help="batched = vmapped multi-client engine; "
                         "looped = per-client reference path")
    ap.add_argument("--kernel-agg", action="store_true",
                    help="aggregate through the Pallas fedagg pytree path")
    ap.add_argument("--window", type=int, default=0,
                    help="async aggregation window: merge up to K "
                         "completions per event drain (fedasync/fedbuff; "
                         "0 = one-at-a-time FedAsync)")
    ap.add_argument("--window-secs", type=float, default=0.0,
                    help="async aggregation window in virtual seconds "
                         "(fedasync/fedbuff; 0 = no time window)")
    ap.add_argument("--no-store", action="store_true",
                    help="async methods only: keep client snapshots as "
                         "a dict of pytrees instead of the "
                         "device-resident flat ClientStateStore "
                         "(reference path, bit-identical histories)")
    ap.add_argument("--hot-rows", type=int, default=0,
                    help="async methods only: tiered client-state "
                         "residency — keep only this many client rows "
                         "on device (hot tier) and the rest in pinned "
                         "host memory, with EventQueue-driven prefetch "
                         "(0 = dense, every row on device; histories "
                         "are bit-identical at any capacity)")
    ap.add_argument("--cold-dir", default=None,
                    help="with --hot-rows: spill the cold tier to "
                         "ckpt-chunk files under this directory "
                         "instead of pinned host memory")
    ap.add_argument("--quant-bits", type=int, default=32,
                    choices=[8, 32],
                    help="async methods only: client-state row format. "
                         "32 = the byte-for-byte f32 store path; 8 = "
                         "int8 quantized rows with per-leaf fused "
                         "scales and server-side error feedback "
                         "(~4x smaller rows and uplink, seeded-"
                         "deterministic, gated convergence delta vs "
                         "f32)")
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="with --quant-bits 8: drop the per-client "
                         "error-feedback residual accumulators "
                         "(ablation — quantization bias goes "
                         "uncorrected)")
    ap.add_argument("--mesh-clients", type=int, default=0,
                    help="shard cohorts over a 1-D client mesh of N "
                         "devices (0 = single-device engine; on CPU "
                         "force devices first with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record runtime telemetry (spans + counters) "
                         "and write the trace here; the aggregate also "
                         "lands in the history's meta['telemetry']")
    ap.add_argument("--trace-format", default="jsonl",
                    choices=["jsonl", "chrome"],
                    help="--trace output format: 'jsonl' = line-delimited "
                         "event log (repro.obs.validate checks it); "
                         "'chrome' = trace_event JSON for "
                         "chrome://tracing / Perfetto")
    ap.add_argument("--report", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="print the per-tier FL run report after the run "
                         "(implies tracing even without --trace); with a "
                         "PATH also write the structured report JSON "
                         "there (see python -m repro.obs.report)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    fl = FLConfig(n_clients=args.clients, n_tiers=args.tiers, tau=args.tau,
                  rounds=args.rounds, mu=args.mu,
                  primary_frac=args.primary_frac, seed=args.seed,
                  lr=1e-3)
    net = WirelessNetwork(fl.n_clients, fl.tier_delay_means, fl.delay_std,
                          fl.mu, fl.failure_delay, fl.seed)
    trainer = build_fl_clients(args.arch, fl)
    kw = dict(verbose=True, engine=args.engine,
              use_kernel_agg=args.kernel_agg)
    if args.mesh_clients > 0:
        from repro.distributed import make_client_mesh
        kw["mesh"] = make_client_mesh(args.mesh_clients)
        print(f"[fl_train] client mesh: {kw['mesh'].size} device(s)")
    if args.method in ("fedasync", "fedbuff"):
        kw["window"] = args.window
        kw["window_secs"] = args.window_secs
    if args.no_store and args.method in ("fedasync", "fedbuff",
                                         "feddct_async"):
        kw["use_store"] = False
    if args.hot_rows > 0 and args.method in ("fedasync", "fedbuff",
                                             "feddct_async"):
        kw["store_capacity"] = args.hot_rows
        kw["store_cold_dir"] = args.cold_dir
    if args.quant_bits != 32 and args.method in ("fedasync", "fedbuff",
                                                 "feddct_async"):
        kw["quant_bits"] = args.quant_bits
        kw["error_feedback"] = not args.no_error_feedback
    if args.trace or args.report is not None:
        from repro import obs
        with obs.tracing() as tel:
            hist = run_method(args.method, trainer, net, fl, **kw)
        if args.trace:
            if args.trace_format == "chrome":
                tel.export_chrome(args.trace)
            else:
                tel.export_jsonl(args.trace)
            print(f"[fl_train] trace ({args.trace_format}) -> {args.trace}")
        if args.report is not None:
            import json as _json

            from repro.obs import report as obs_report
            rep = obs_report.build_report(hist.meta["telemetry"],
                                          hist.to_json())
            print(obs_report.format_report(rep, source=args.method))
            if args.report != "-":
                with open(args.report, "w") as f:
                    _json.dump(rep, f, indent=2, sort_keys=True)
                print(f"[fl_train] report json -> {args.report}")
    else:
        hist = run_method(args.method, trainer, net, fl, **kw)
    if hist.accuracy:
        print(f"[fl_train] {args.method} on {args.arch}: "
              f"final acc={hist.accuracy[-1]:.4f} "
              f"virtual time={hist.times[-1]:.1f}s")
    else:
        print(f"[fl_train] {args.method} on {args.arch}: finished before "
              f"the first evaluation (fewer updates than eval_every)")
    if args.out:
        hist.save(args.out)
        print(f"[fl_train] history -> {args.out}")
    return hist


if __name__ == "__main__":
    main()
