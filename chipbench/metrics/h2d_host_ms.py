"""Host milliseconds per round in the trainer's ``train.h2d`` spans:
the ``jnp.asarray`` of the cohort's stacked batches.  The copy is
asynchronous, so this is the host's share of it; a copy still landing
after the span closes shows as device idle time under later spans."""

from chipbench.metrics._common import span_ms_per_round

UNIT, LAYER, MOVES = "ms", "trainer input", "round_s"


def read(ctx):
    return span_ms_per_round(ctx, {"train.h2d"})
