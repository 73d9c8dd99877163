"""Share of the HBM roofline the fedagg merge kernels reach: the bytes
the merges of the window need, over their device time times the chip's
HBM bandwidth.  The merge is bandwidth-bound (two FLOPs per element
read), so bandwidth bounds it.  Kernel time is that of the Pallas
custom calls inside the fedagg programs, on the busiest device."""

from chipbench import costs

UNIT, LAYER, MOVES = "%", "merge", "updates_per_s"


def read(ctx):
    ns = max((d["fedagg_kernel_ns"] for d in ctx.devices), default=0)
    need = costs.fedagg_window_bytes(ctx.round_updates, ctx.n_params)
    if not ns or not need:
        return None
    return 100.0 * need / (ns / 1e9 * ctx.peaks["hbm_bytes_per_s"])
