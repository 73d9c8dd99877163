"""Device milliseconds per round of the vmapped cohort-training
programs, on the busiest device of the trace."""

from chipbench.metrics._common import TRAIN_PROGRAMS, busiest_ns

UNIT, LAYER, MOVES = "ms", "engine", "round_s"


def read(ctx):
    ns = busiest_ns(ctx, TRAIN_PROGRAMS)
    return ns / 1e6 / ctx.rounds if ns and ctx.rounds else None
