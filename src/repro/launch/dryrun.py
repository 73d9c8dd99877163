import os

from repro.distributed.hostdevices import ensure_host_device_count

# 512 forced host devices for the multi-pod production meshes.  This
# APPENDS to any XLA_FLAGS the caller already exported (and an existing
# --xla_force_host_platform_device_count wins) instead of clobbering
# the variable — the forced-device-count CI job and local debugging
# flags survive importing this module.  It must still run before jax
# initializes its backend: the device count locks on first backend init.
ensure_host_device_count(512)

"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production meshes, record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out benchmarks/results/dryrun
"""

import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax
import numpy as np

from repro.config import get_arch
from repro.config.base import INPUT_SHAPES, InputShape, ModelConfig, TrainConfig
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_production_mesh
from repro.roofline import TPU_V5E, analyze_hlo, roofline_terms
from repro.sharding import (batch_specs, decode_state_specs, named_shardings,
                            param_specs)
from repro.sharding.hints import set_mesh

ASSIGNED = [
    "granite-20b", "nemotron-4-340b", "phi4-mini-3.8b", "llama3.2-1b",
    "mixtral-8x7b", "hubert-xlarge", "hymba-1.5b", "arctic-480b",
    "xlstm-350m", "chameleon-34b",
]

# The BASELINE sharding config for the roofline table: megatron TP + FSDP
# without any of the §Perf hillclimb optimizations (those are recorded
# separately by benchmarks/perf_iterate.py).
BASELINE_TCFG = TrainConfig(context_parallel="never", seq_parallel=False,
                            long_ctx_swa=False, decode_headdim_shard=False)


def skip_reason(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    if cfg.is_encoder_only and shape.kind == "decode":
        return "encoder-only: no decode step (DESIGN.md §6)"
    return None


def variant_note(cfg: ModelConfig, shape: InputShape,
                 tcfg: TrainConfig) -> str:
    if steps_lib.swa_window_for(cfg, shape, enabled=tcfg.long_ctx_swa) > 0:
        return f"swa-{steps_lib.SWA_OVERRIDE_WINDOW}"
    return "native"


def run_one(arch: str, shape_name: str, multi_pod: bool,
            tcfg: TrainConfig = None, verbose: bool = True
            ) -> Dict:
    if tcfg is None:
        tcfg = BASELINE_TCFG
    from repro.models import attention as _attn
    _attn.DECODE_HEADDIM_SHARD = tcfg.decode_headdim_shard
    cfg = get_arch(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "variant": variant_note(cfg, shape, tcfg)}
    reason = skip_reason(cfg, shape)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    set_mesh(mesh, fsdp_only=tcfg.parallelism == "fsdp_only")
    n_chips = int(np.prod(mesh.devices.shape))
    t0 = time.time()

    params = steps_lib.abstract_params(cfg, tcfg)
    p_specs = param_specs(params, mesh, fsdp=tcfg.fsdp,
                          mode=tcfg.parallelism)
    p_shard = named_shardings(p_specs, mesh)
    batch = steps_lib.input_specs(cfg, shape, tcfg)
    b_shard = named_shardings(batch_specs(batch, mesh,
                                          mode=tcfg.parallelism), mesh)

    if shape.kind == "train":
        opt_state = steps_lib.abstract_opt_state(cfg, tcfg)
        o_specs = _opt_specs(opt_state, params, mesh, tcfg)
        o_shard = named_shardings(o_specs, mesh)
        step, _ = steps_lib.make_train_step(cfg, tcfg)
        fn = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                     out_shardings=(p_shard, o_shard, None))
        with mesh:
            lowered = fn.lower(params, opt_state, batch)
    elif shape.kind == "prefill":
        step = steps_lib.make_prefill_step(cfg, tcfg)
        fn = jax.jit(step, in_shardings=(p_shard, b_shard),
                     out_shardings=None)
        with mesh:
            lowered = fn.lower(params, batch)
    else:  # decode
        state = steps_lib.abstract_decode_state(cfg, shape, tcfg)
        s_shard = named_shardings(decode_state_specs(state, mesh), mesh)
        step = steps_lib.make_serve_step(cfg, shape, tcfg)
        fn = jax.jit(step, in_shardings=(p_shard, s_shard, b_shard),
                     out_shardings=(None, s_shard))
        with mesh:
            lowered = fn.lower(params, state, batch)

    with mesh:
        compiled = lowered.compile()
    set_mesh(None)
    rec["compile_s"] = round(time.time() - t0, 1)

    mem = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes_per_device": int(mem.argument_size_in_bytes),
        "output_bytes_per_device": int(mem.output_size_in_bytes),
        "temp_bytes_per_device": int(mem.temp_size_in_bytes),
        "alias_bytes_per_device": int(mem.alias_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):      # older jax: one dict per device
        ca = ca[0] if ca else {}
    rec["xla_cost"] = {"flops": float(ca.get("flops", 0.0)),
                       "bytes_accessed": float(ca.get("bytes accessed", 0.0))}

    hlo = analyze_hlo(compiled.as_text())
    rec["hlo"] = {k: (v if not isinstance(v, dict) else v)
                  for k, v in hlo.items()}

    # --- roofline (per-chip quantities parsed from SPMD HLO) -----------
    # memory term: bytes-accessed from cost_analysis undercounts scanned
    # bodies exactly like flops do; scale it by the same ratio.
    flops_pc = hlo["dot_flops"]
    xla_flops = max(rec["xla_cost"]["flops"], 1.0)
    scan_ratio = max(flops_pc / xla_flops, 1.0)
    bytes_pc = rec["xla_cost"]["bytes_accessed"] * scan_ratio
    terms = roofline_terms(hlo_flops=flops_pc, hbm_bytes=bytes_pc,
                           collective_bytes=hlo["collective_wire_bytes"],
                           chips=1, hw=TPU_V5E)
    mf = steps_lib.model_flops(cfg, shape)
    terms["model_flops_global"] = mf
    terms["hlo_flops_global"] = flops_pc * n_chips
    terms["useful_ratio"] = mf / max(flops_pc * n_chips, 1.0)
    rec["roofline"] = terms
    rec["status"] = "ok"
    if verbose:
        print(f"[dryrun] {arch:16s} {shape_name:12s} {mesh_name:8s} "
              f"{rec['variant']:10s} compile={rec['compile_s']:6.1f}s "
              f"dom={terms['dominant']:12s} bound={terms['bound_s']:.4f}s "
              f"useful={terms['useful_ratio']:.2f}", flush=True)
    return rec


def _opt_specs(opt_state, params, mesh, tcfg):
    """Optimizer moments shard like their parameter; scalars replicate."""
    from jax.sharding import PartitionSpec as P
    p_specs = param_specs(params, mesh, fsdp=tcfg.fsdp,
                          mode=tcfg.parallelism)

    def match(o_leaf_path, o_leaf):
        return None

    # adam state: {"m": tree, "v": tree, "t": scalar}
    if isinstance(opt_state, dict) and "m" in opt_state:
        return {"m": p_specs, "v": p_specs, "t": P()}
    if isinstance(opt_state, tuple) and len(opt_state) == 0:
        return ()
    return jax.tree_util.tree_map(lambda _: P(), opt_state)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    args = ap.parse_args()

    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'multi' if mp else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[dryrun] {tag}: cached")
                    results.append(json.load(open(path)))
                    continue
                try:
                    rec = run_one(arch, shape, mp)
                except Exception as e:  # fedlint: disable=FED007 -- sweep harness records the per-arch failure and continues
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error", "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                    print(f"[dryrun] {tag}: ERROR {e}", flush=True)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                results.append(rec)
    ok = sum(1 for r in results if r.get("status") == "ok")
    sk = sum(1 for r in results if r.get("status") == "skipped")
    er = sum(1 for r in results if r.get("status") == "error")
    print(f"[dryrun] done: {ok} ok, {sk} skipped, {er} errors")
    return 0 if er == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
