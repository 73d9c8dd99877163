#!/usr/bin/env python3
"""Readings that the correctness limits of ``cells/<cell>.json`` are set
from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 3 \\
        [--stand-ins control,half_batch,stale_start,...] [--out F]

For every seed, in one process: one short run of the cell (the
program's own numbers), then, on the very rounds that run captured,
each stand-in put in the program's place and compared in the same way:

* ``control``: the reference computed in bfloat16 throughout (params,
  activations, optimizer state and the merge), one precision below the
  float32 the configuration states;
* ``unchanged``: every client's training returns its start model;
* ``half_batch``: each local batch's second half left out, the mean
  loss taken over the rest;
* ``half_cohort``: the merge leaves out the second half of the round's
  clients and takes its mean over the rest;
* ``altered``: the merged global model with one weight changed by a
  hundredth of its leaf's largest value;
* ``stale_start`` (traffic merged by staleness): every client trains
  from the global model one round before the one that selected it;
* ``no_staleness`` (same): the merge folds every row with ``alpha``,
  its staleness ignored.

The merges are the traffic's reference merge (``sync_mean`` or
``staleness_fold``), over the rows the stand-in puts in place.

Prints one JSON line per seed (and appends it to ``--out``), with each
compared client's gap under ``clients``.  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
STAND_INS = ("control", "unchanged", "half_batch", "half_cohort", "altered")
TRACKED = ("stale_start", "no_staleness")     # traffic merged by staleness


def stand_in(kind: str, cell: dict, trainer, captures) -> dict:
    """Round -> {"rows", "g_out"} (and "starts") of ``kind`` put in the
    program's place."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import data, harness
    from chipbench import reference as ref

    cfg, traffic = cell["config_data"], cell["traffic_data"]
    merge = traffic.get("merge", "sync_mean")
    fed = {**cfg["federation"], **traffic.get("federation", {})}
    model = ref.model_module(cfg["model"])
    stated = ref.precision_of(cfg["matmul_precision"])
    trainers = {
        "control": lambda: ref.make_train(model, cfg["sizes"], fed["lr"],
                                          dtype=jnp.bfloat16,
                                          precision=jax.lax.Precision.DEFAULT),
        "half_batch": lambda: ref.make_train(model, cfg["sizes"], fed["lr"],
                                             precision=stated,
                                             half_batch=True)}
    train = trainers[kind]() if kind in trainers else None
    block = harness.ref_block(cfg)
    ds = data.module(cfg)
    samples = ds.clients(cfg)
    treedef = jax.tree_util.tree_structure(captures[0].g_in)
    out = {}
    for cap in captures:
        entries = harness.round_inputs(cap, ds, samples)
        rows = [e.row for e in entries]
        w = [e.weight for e in entries]
        g_in = ref.leaves64(cap.g_in)
        stale = [(e.selection.staleness if e.selection else None) or 0
                 for e in entries]
        alphas = ref.staleness_alphas(fed, stale) if cap.merged else None
        alt = {}
        if train is not None:
            rows = []
            for i in range(0, len(entries), block):
                chunk = entries[i:i + block]
                st = [ds.client_stream(cfg, samples[e.client], e.seed)
                      for e in chunk]
                starts = jax.tree_util.tree_unflatten(treedef, [
                    jnp.asarray(np.stack([e.start[j] for e in chunk]),
                                jnp.float32)
                    for j in range(len(chunk[0].start))])
                got = train(starts, jnp.asarray(np.stack([x for x, _ in st])),
                            jnp.asarray(np.stack([y for _, y in st])))
                rows += [ref.row_of(got, k) for k in range(len(chunk))]
        elif kind == "unchanged":
            rows = [e.start for e in entries]
        elif kind == "stale_start":
            alt["starts"] = [
                ref.leaves64(e.selection.before)
                if e.selection and e.selection.before is not None
                else e.start for e in entries]
        if kind == "control":
            g_out = ref.merge(merge, g_in, rows, w, alphas, dtype=jnp.bfloat16)
        elif kind == "half_cohort":
            k = len(rows) - len(rows) // 2
            g_out = ref.merge(merge, g_in, rows[:k], w[:k],
                              alphas[:k] if alphas else None)
        elif kind == "altered":
            g_out = ref.leaves64(cap.g_out)
            big = max(range(len(g_out)), key=lambda j: g_out[j].size)
            flat = g_out[big].reshape(-1)
            flat[0] += 0.01 * float(np.max(np.abs(flat)))
        elif kind == "no_staleness":
            g_out = ref.merge(merge, g_in, rows, w,
                              ref.staleness_alphas(fed, [0] * len(rows)))
        else:
            g_out = ref.merge(merge, g_in, rows, w, alphas)
        out[cap.index] = dict(alt, rows=rows, g_out=g_out)
    return out


def default_stand_ins(cell: dict) -> list:
    from chipbench import harness
    tracked = harness.tracks_selection(cell["traffic_data"])
    return list(STAND_INS) + (list(TRACKED) if tracked else [])


def calibrate(cell: dict, seeds, seconds: float, kinds, log,
              require_tpu: bool = True, precisions=(None,)):
    """Yields one line of readings per seed (module docstring).  Each
    of ``precisions`` (``None``: the configuration's) is a matmul
    precision of the reference to read the numbers against."""
    from chipbench import harness
    cfg = cell["config_data"]
    for seed in seeds:
        keep = {}
        res = harness.run(cell["name"], seed, seconds, False,
                          time.perf_counter(), log, cell=cell, keep=keep,
                          require_tpu=require_tpu)
        line = {"workload": cell["name"], "seed": seed,
                "correct": res["correct"], "program": keep.get("nums", {})}
        if not keep.get("captures"):
            yield line
            continue
        args = (cfg, keep["trainer"], keep["captures"], seed)
        kw = {"traffic": cell["traffic_data"]}
        alts = {k: stand_in(k, cell, keep["trainer"], keep["captures"])
                for k in kinds}
        line["clients"] = {}
        for prec in precisions:
            tag = "" if prec is None else f"@{prec}"
            for kind, alt in [("program", None)] + list(alts.items()):
                detail = []
                line[kind + tag] = harness.compare(
                    *args, stand_in=alt, precision=prec, detail=detail, **kw)
                line["clients"][kind + tag] = detail
        yield line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--stand-ins", default=None,
                    help="comma list; default every stand-in the cell's "
                         "traffic can have")
    ap.add_argument("--out", default=None)
    ap.add_argument("--precisions", default="",
                    help="comma list of reference matmul precisions to read "
                         "against besides the configuration's")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from chipbench import run, spec
    run.compile_cache()
    log = lambda m: print(m, file=sys.stderr, flush=True)
    cell = spec.cell(args.workload)
    kinds = ([k for k in args.stand_ins.split(",") if k]
             if args.stand_ins is not None else default_stand_ins(cell))
    seeds = [int(s) for s in args.seeds.split(",")]
    precisions = [None] + [p for p in args.precisions.split(",") if p]
    for line in calibrate(cell, seeds, args.seconds,
                          kinds, log, precisions=precisions):
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
