"""Device milliseconds per round in the client-state store's programs:
the cohort's snapshot gather (``gather_impl``), the singleton's
(``gather_one_impl``) and the flatten-and-scatter of a global row into
the selected or merged clients' slots (``scatter_params_impl``, the
scatter half of ``merge_scatter``), on the busiest device."""

from chipbench.metrics._common import busiest_ns

UNIT, LAYER, MOVES = "ms", "store", "round_s"
PROGRAMS = ("gather_impl", "gather_one_impl", "scatter_params_impl")


def read(ctx):
    ns = busiest_ns(ctx, PROGRAMS)
    return ns / 1e6 / ctx.rounds if ns and ctx.rounds else None
