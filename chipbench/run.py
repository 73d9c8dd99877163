#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell that ``BENCHMARK.json`` names from its files under
``chipbench/``, warms every program its traffic can dispatch, measures
the program's normal entry point for ``--seconds``, checks what the
window produced against the plain reference, and prints one JSON
object as the last line of standard output.  With ``--trace 0`` it
reports the cell's end-to-end metrics; with ``--trace 1`` it runs the
same window, traces its last six seconds with the JAX profiler and
reports the per-layer metrics, the device's busy time and a breakdown.

Without a TPU, with fewer chips than the cell asks for, or outside a
checkout of the repository, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
TRACE_DIR = ROOT / "chipbench_out" / "trace"


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def compile_cache():
    """JAX's persistent compilation cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``),
    holding every program the cell compiles, however quick."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    log(f"[cache] {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"chipbench: no repro package under {ROOT / 'src'}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    compile_cache()

    from chipbench import harness
    trace_dir = None
    try:
        cell = harness.spec.cell(args.workload)
        if args.trace:
            trace_dir = str(TRACE_DIR / f"{args.workload}-{args.seed}")
            os.makedirs(trace_dir, exist_ok=True)
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_PROCESS, log, cell=cell,
                          trace_dir=trace_dir)
    except harness.SetupError as e:
        log(f"chipbench: {e}")
        return 1
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    for name, c in out["check"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
