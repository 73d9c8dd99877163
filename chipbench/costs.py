"""Operations and bytes the benchmark counts from shapes, never from
the compiled program: the merge's needed HBM traffic and the model
FLOPs of local training."""

from __future__ import annotations

from typing import Sequence

from chipbench import reference as ref

F32 = 4


def fedagg_bytes(rows: int, p: int) -> int:
    """HBM bytes one average of ``rows`` real client rows of ``p`` f32
    params needs: each real row read once, one merged row written.
    Padded rows that carry a zero coefficient are not needed and are
    not counted."""
    return (rows + 1) * p * F32


def fedagg_window_bytes(round_updates: Sequence[int], p: int) -> int:
    """Needed bytes of every kernel merge of the window's sync rounds:
    one average of the survivors in each round where any survived."""
    return sum(fedagg_bytes(n, p) for n in round_updates if n >= 1)


def fedagg_fold_bytes(rows: int, p: int) -> int:
    """HBM bytes one folded staleness merge (``fedagg_fold``) of ``rows``
    real client rows into the global row needs: each real row and the
    global read once, one merged row written.  Padded rows carry a zero
    coefficient and are not counted."""
    return (rows + 2) * p * F32


def fold_window_bytes(round_updates: Sequence[int], p: int) -> int:
    """Needed bytes of every fold of the window's semi-async rounds: a
    round that merges two rows or more folds them in one kernel pass; a
    round of one row takes the singleton merge and no kernel."""
    return sum(fedagg_fold_bytes(n, p) for n in round_updates if n >= 2)


def flops_per_sample(cfg: dict) -> int:
    return ref.model_module(cfg["model"]).flops_per_sample(cfg["sizes"])


def training_flops(cfg: dict, client_updates: int) -> int:
    """Model FLOPs of training ``client_updates`` clients for one round
    each: local steps x batch x forward+backward FLOPs per sample."""
    fed = cfg["federation"]
    return (client_updates * cfg["local_steps"] * fed["batch_size"]
            * flops_per_sample(cfg))
