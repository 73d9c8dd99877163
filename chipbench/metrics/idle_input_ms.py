"""Device-idle milliseconds per round while the host builds the
cohort's batches, copies them to the device and dispatches the cohort
program: the idle gaps under the union of the trainer's
``train.batches``, ``train.h2d`` and ``train.dispatch`` spans, averaged
over the cell's chips as ``idle_share`` is."""

from chipbench import trace

UNIT, LAYER, MOVES = "ms", "device", "round_s"
INPUT_SPANS = ("train.batches", "train.h2d", "train.dispatch")


def intersect(xs, ys):
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            out.append((lo, hi))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_ms_per_round(ctx, under):
    """Device-idle ms per round inside the sorted disjoint intervals
    ``under``, averaged over the cell's chips."""
    ns = sum(trace.length(intersect(d["gaps"], under)) for d in ctx.devices)
    return ns / len(ctx.devices) / 1e6 / ctx.rounds


def read(ctx):
    under = trace.union((t0, t1) for n, t0, t1 in ctx.spans
                        if n in INPUT_SPANS)
    if not under or not ctx.devices or not ctx.rounds:
        return None
    return idle_ms_per_round(ctx, under)
