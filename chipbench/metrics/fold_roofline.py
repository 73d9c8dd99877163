"""Share of the HBM roofline the folded staleness merge kernel
(``fedagg_fold``) reaches: the bytes the window's folds need (each real
row and the global read once, one merged row written), over the time of
the Pallas custom calls inside the ``_fedagg_fold_call`` programs times
the chip's HBM bandwidth, on the busiest device."""

from chipbench import costs

UNIT, LAYER, MOVES = "%", "merge", "updates_per_s"
PROGRAMS = ("_fedagg_fold_call",)


def read(ctx):
    ns = max((sum(t for name, t in d.get("custom_call_ns", {}).items()
                  if any(k in name for k in PROGRAMS))
              for d in ctx.devices), default=0)
    need = costs.fold_window_bytes(ctx.round_updates, ctx.n_params)
    if not ns or not need:
        return None
    return 100.0 * need / (ns / 1e9 * ctx.peaks["hbm_bytes_per_s"])
