"""Host milliseconds per round in the runners' ``round.select`` spans:
tiering, CSTT and the delay draws (host-only work)."""

from chipbench.metrics._common import span_ms_per_round

UNIT, LAYER, MOVES = "ms", "runners", "round_s"


def read(ctx):
    return span_ms_per_round(ctx, {"round.select"})
