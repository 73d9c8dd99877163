"""``feddct_async`` on the dense client-state store: a round snapshots
its selection (at most tau from each tier) in one scatter, then merges
what completed inside its window, carried stragglers included, so 1 to
``n_clients`` rows.  One row takes the singleton path
(``train_clients``, ``staleness_merge``, ``scatter_params``); more rows
one pow2 cohort bucket of the store's gather, the cohort program, the
folded merge and its scatter.  The windows go through the runner's own
window step, on a store of the warm-up's own, with telemetry on so that
a traced run's update-norm program is warm too."""

from __future__ import annotations

from typing import Dict

from chipbench.warmups import engine

RUNNER_KEYS = ("use_kernel_agg",)


def warm(cfg: dict, traffic: dict, trainer, params):
    import jax

    from repro import obs
    from repro.core.state import ClientStateStore
    from repro.runtime.async_loop import _merge_window_store
    from repro.runtime.events import ClientEvent

    from chipbench.harness import fl_config

    fed = cfg["federation"]
    n_clients = fed["n_clients"]
    fl = fl_config(cfg, traffic=traffic)
    eng = engine(trainer, traffic)
    store = ClientStateStore(params, n_clients)
    out = [trainer.evaluate(params)]
    for k in range(1, min(fed["tau"] * fed["n_tiers"], n_clients) + 1):
        out.append(store.scatter_params(list(range(k)), params))
    by_bucket: Dict[int, int] = {}
    for n in range(1, n_clients + 1):
        by_bucket.setdefault(eng._pad_target(n), n)
    with obs.tracing():
        for n in sorted(by_bucket.values()):
            batch = [ClientEvent(float(c), c, 0, 1, cost=1.0)
                     for c in range(n)]
            out.append(_merge_window_store(eng, store, params, batch, fl,
                                           n))
        jax.block_until_ready(out)
    del store
