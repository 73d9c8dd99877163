"""Host milliseconds per round in the engine's ``round.train`` spans:
batch assembly in numpy, the host-to-device copy and the dispatch of
the cohort program.  ``evaluate`` synced the device at the round's
start, so this is host work."""

from chipbench.metrics._common import span_ms_per_round

UNIT, LAYER, MOVES = "ms", "trainer input", "round_s"


def read(ctx):
    return span_ms_per_round(ctx, {"round.train"})
