"""Device-idle milliseconds per round that no program span names: the
idle gaps inside the runners' ``round`` spans and under no other span
of the program, averaged over the cell's chips as ``idle_share`` is.
The benchmark's own ``trainer.*`` spans and the whole-run ``run`` span
attribute nothing.  This is what the measurement cannot see yet."""

from chipbench import trace
from chipbench.metrics.idle_input_ms import idle_ms_per_round, intersect

UNIT, LAYER, MOVES = "ms", "device", "round_s"
NOT_ATTRIBUTION = ("round", "run")


def read(ctx):
    rounds = trace.union((t0, t1) for n, t0, t1 in ctx.spans
                         if n == "round")
    if not rounds or not ctx.devices or not ctx.rounds:
        return None
    named = trace.union((t0, t1) for n, t0, t1 in ctx.spans
                        if n not in NOT_ATTRIBUTION
                        and not n.startswith("trainer."))
    return (idle_ms_per_round(ctx, rounds)
            - idle_ms_per_round(ctx, intersect(rounds, named)))
