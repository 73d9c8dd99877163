"""Device milliseconds per round of the vmapped cohort-training
programs (``_batch_train_impl``, ``_batch_train_multi_impl``), on the
busiest device of the trace.  The gather of their batches from the
sample table (``_gather_batches``) is not counted."""

from chipbench.metrics._common import TRAIN_PROGRAMS, busiest_ns

UNIT, LAYER, MOVES = "ms", "engine", "round_s"


def read(ctx):
    ns = busiest_ns(ctx, TRAIN_PROGRAMS)
    return ns / 1e6 / ctx.rounds if ns and ctx.rounds else None
