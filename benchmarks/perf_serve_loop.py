import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf arctic v4: 16-step serve loop — does XLA hoist the FSDP weight
gathers out of the decode scan (amortizing them across tokens)?

    PYTHONPATH=src python -m benchmarks.perf_serve_loop

Roofline one-off: writes its own results/perf/ records and stays
outside the ``BENCH_*.json`` / ``compare.py`` bench trajectory.
"""

import json

import jax

from repro.config import get_arch
from repro.config.base import INPUT_SHAPES, TrainConfig
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_production_mesh
from repro.roofline import TPU_V5E, analyze_hlo, roofline_terms
from repro.sharding import (batch_specs, decode_state_specs, named_shardings,
                            param_specs)
from repro.sharding.hints import set_mesh

N_STEPS = 16


def run(arch, shape_name, fsdp: bool):
    cfg = get_arch(arch)
    shape = INPUT_SHAPES[shape_name]
    tcfg = TrainConfig(context_parallel="never", seq_parallel=False,
                       long_ctx_swa=True, decode_headdim_shard=False,
                       fsdp=fsdp)
    mesh = make_production_mesh()
    set_mesh(mesh)
    params = steps_lib.abstract_params(cfg, tcfg)
    p_sh = named_shardings(param_specs(params, mesh, fsdp=tcfg.fsdp), mesh)
    state = steps_lib.abstract_decode_state(cfg, shape, tcfg)
    s_sh = named_shardings(decode_state_specs(state, mesh), mesh)
    batch = steps_lib.input_specs(cfg, shape, tcfg)
    b_sh = named_shardings(batch_specs(batch, mesh), mesh)
    loop = steps_lib.make_serve_loop(cfg, shape, tcfg, n_steps=N_STEPS)
    fn = jax.jit(loop, in_shardings=(p_sh, s_sh, b_sh),
                 out_shardings=(None, s_sh))
    with mesh:
        compiled = fn.lower(params, state, batch).compile()
    set_mesh(None)
    hlo = analyze_hlo(compiled.as_text())
    terms = roofline_terms(
        hlo_flops=hlo["dot_flops"],
        hbm_bytes=0.0,
        collective_bytes=hlo["collective_wire_bytes"], chips=1, hw=TPU_V5E)
    per_tok = terms["collective_s"] / N_STEPS
    print(f"[serve_loop] {arch} {shape_name} fsdp={fsdp}: "
          f"collective {terms['collective_s']:.4f}s / {N_STEPS} steps "
          f"= {per_tok:.4f}s/token", flush=True)
    return {"arch": arch, "shape": shape_name, "fsdp": fsdp,
            "n_steps": N_STEPS, "collective_s_total": terms["collective_s"],
            "collective_s_per_token": per_tok,
            "collective_breakdown": hlo["collective_breakdown"]}


def main():
    out = []
    for fsdp in (True, False):
        out.append(run("arctic-480b", "long_500k", fsdp))
    os.makedirs("benchmarks/results/perf", exist_ok=True)
    with open("benchmarks/results/perf/arctic-480b_long_500k_v4_serveloop.json",
              "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
