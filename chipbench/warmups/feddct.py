"""``feddct``: a round trains its survivors (at most tau from each tier)
in a pow2 bucket and merges as many rows as survived."""

from __future__ import annotations

from typing import Dict, List

from chipbench.warmups import engine

RUNNER_KEYS = ("use_kernel_agg",)


def warm(cfg: dict, traffic: dict, trainer, params):
    import jax

    fed = cfg["federation"]
    top = min(fed["tau"] * fed["n_tiers"], fed["n_clients"])
    eng = engine(trainer, traffic)
    by_bucket: Dict[int, List[int]] = {}
    for n in range(1, top + 1):
        by_bucket.setdefault(eng._pad_target(n), []).append(n)
    ids = [c % fed["n_clients"] for c in range(max(by_bucket))]
    out = [trainer.evaluate(params)]
    for b, ns in sorted(by_bucket.items()):
        stacked, sizes = eng._local_train_batch(params, ids[:b], 10 ** 6)
        for n in ns:
            rows = (stacked if n == b else
                    jax.tree_util.tree_map(lambda l, n=n: l[:n], stacked))
            out.append(eng.aggregate_or_keep(params, rows, sizes[:n]))
    jax.block_until_ready(out)
