"""Host milliseconds per round in the trainer's ``train.batches``
spans: building each client's batch stream in numpy and stacking the
cohort's streams per shape bucket.  A child of ``round.train``."""

from chipbench.metrics._common import span_ms_per_round

UNIT, LAYER, MOVES = "ms", "trainer input", "round_s"


def read(ctx):
    return span_ms_per_round(ctx, {"train.batches"})
