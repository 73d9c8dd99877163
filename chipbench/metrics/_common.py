"""Shared arithmetic of the per-layer readers."""

from __future__ import annotations

from chipbench import trace

# the sync and singleton program, and the cohort program from per-client
# start models (semi-async windows)
TRAIN_PROGRAMS = ("_batch_train_impl", "_batch_train_multi_impl")


def span_ms_per_round(ctx, names) -> float | None:
    total = sum(t1 - t0 for n, t0, t1 in ctx.spans if n in names)
    if not ctx.rounds or total <= 0:
        return None
    return total / 1e6 / ctx.rounds


def busiest_ns(ctx, needles) -> int:
    """Device ns of the programs named by ``needles``, busiest device."""
    return max((trace.time_in(d["modules_ns"], needles) for d in ctx.devices),
               default=0)
