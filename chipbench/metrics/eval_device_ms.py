"""Device milliseconds per round of the evaluation program
(``_eval_impl``: the global model's forward pass over a chunk of the
test split, four chunks of 512 a round), on the busiest device of the
trace."""

from chipbench.metrics._common import busiest_ns

UNIT, LAYER, MOVES = "ms", "evaluation", "round_s"
PROGRAMS = ("_eval_impl",)


def read(ctx):
    ns = busiest_ns(ctx, PROGRAMS)
    return ns / 1e6 / ctx.rounds if ns and ctx.rounds else None
