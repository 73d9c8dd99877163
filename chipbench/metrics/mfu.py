"""The whole step's share of the chip's bf16 peak: the model FLOPs of
local training in the window (clients trained x local steps x batch x
forward+backward FLOPs per sample, from the configuration's layer
shapes; the optimizer is not counted) over window time x chips x
peak."""

from chipbench import costs

UNIT, LAYER, MOVES = "%", "whole step", "updates_per_s"


def read(ctx):
    flops = costs.training_flops(ctx.cell["config_data"],
                                 sum(ctx.round_updates))
    if not flops or ctx.window_s <= 0:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peaks["bf16_flops_per_s"])
