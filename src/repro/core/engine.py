"""Batched multi-client execution engine — the server's round hot path.

The seed implementation trained selected clients one at a time in a
Python ``for`` loop and aggregated a Python list of per-client pytrees.
That caps real wall-clock throughput at ``C * T`` eager dispatches per
round, so the paper's simulated-time gains never became real-time
gains.  ``BatchedClientEngine`` replaces that:

* local training for the whole cohort runs as ONE jitted program
  (``trainer.local_train_batch``: vmap over clients of a lax.scan over
  local steps) producing a stacked update pytree with a leading client
  axis — no per-client host round-trips;
* aggregation reduces the stacked pytree on device
  (``weighted_average_stacked``), optionally through the pytree-native
  Pallas fedagg path (single flattened (N, P) kernel pass with fused
  weight normalization + straggler masking).

Trainers that cannot batch (no ``local_train_batch``, or a custom pjit
step) transparently fall back to the looped path with identical
semantics, so schedulers are written against the engine only.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import (aggregate_or_keep,
                                    staleness_merge_coefficients,
                                    staleness_weighted_merge,
                                    weighted_average_stacked)
from repro.kernels.ops import resolve_interpret
from repro.obs import flstats
from repro.obs import telemetry as obs


class BatchedClientEngine:
    """Executes a cohort of clients and aggregates them without leaving
    device.  One instance per run (it owns no model state)."""

    def __init__(self, trainer, *, use_kernel_agg: bool = False,
                 interpret: Optional[bool] = None,
                 force_looped: bool = False, pad_cohorts: bool = True):
        self.trainer = trainer
        self.use_kernel_agg = use_kernel_agg
        # resolved once: Pallas interpret mode only on a CPU backend
        self.interpret = resolve_interpret(interpret)
        self.force_looped = force_looped
        # batched paths the trainer refused at run time (it raised
        # NotImplementedError and the engine fell back to looping)
        self.fallbacks: list = []
        # pad cohort size up to a power of two so jit retraces O(log C)
        # distinct shapes instead of one per cohort size; pad rows are
        # duplicates of the last client and are sliced off again.
        self.pad_cohorts = pad_cohorts
        self._can_batch = (not force_looped
                           and hasattr(trainer, "local_train_batch"))
        self._can_cohort = (not force_looped
                            and hasattr(trainer, "local_train_cohort"))

    # -- local training -------------------------------------------------
    def _pad_target(self, n: int) -> int:
        """Padded cohort size for ``n`` clients (subclass hook: the
        sharded engine also rounds up to a mesh multiple)."""
        return 1 << (n - 1).bit_length()

    def _pad_pow2(self, *lists):
        """Pad parallel per-client lists up to ``_pad_target`` by
        repeating their last element (see ``pad_cohorts``)."""
        if not self.pad_cohorts:
            return lists
        n = len(lists[0])
        target = self._pad_target(n)
        return tuple(l + [l[-1]] * (target - n) for l in lists)

    def _local_train_batch(self, params, ids, rnd_seed):
        """Trainer dispatch hook (the sharded engine injects its
        ``wrap`` here)."""
        return self.trainer.local_train_batch(params, ids, rnd_seed)

    def _local_train_cohort(self, stacked_starts, ids, seeds):
        return self.trainer.local_train_cohort(stacked_starts, ids, seeds)

    def train_clients(self, params, client_ids: Sequence[int],
                      rnd_seed: int):
        """-> (stacked update pytree with leading axis len(client_ids),
        sizes (len(client_ids),) f32).  Empty cohort -> (None, empty)."""
        ids = [int(c) for c in client_ids]
        if not ids:
            return None, np.zeros((0,), np.float32)
        if self._can_batch:
            n = len(ids)
            (run_ids,) = self._pad_pow2(ids)
            try:
                stacked, sizes = self._local_train_batch(
                    params, run_ids, rnd_seed)
                if len(run_ids) != n:
                    stacked = jax.tree_util.tree_map(
                        lambda l: l[:n], stacked)
                    sizes = sizes[:n]
                return stacked, sizes
            except NotImplementedError:
                self._can_batch = False
                self.fallbacks.append("train_clients")
        outs = [self.trainer.local_train(params, c, rnd_seed=rnd_seed)
                for c in ids]
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[p for p, _ in outs])
        sizes = np.asarray([s for _, s in outs], np.float32)
        return stacked, sizes

    def train_cohort(self, start_params: Sequence, client_ids: Sequence[int],
                     rnd_seeds: Sequence[int]):
        """Async-window cohort: client i trains from its OWN snapshot
        ``start_params[i]`` with its own data-stream seed.

        -> (stacked update pytree with leading axis len(client_ids),
        sizes (len(client_ids),) f32).  Empty cohort -> (None, empty).
        Falls back to looping ``local_train`` per client when the
        trainer lacks ``local_train_cohort``.
        """
        ids = [int(c) for c in client_ids]
        seeds = [int(s) for s in rnd_seeds]
        starts = list(start_params)
        if not ids:
            return None, np.zeros((0,), np.float32)
        if self._can_cohort:
            n = len(ids)
            run_ids, run_seeds, run_starts = self._pad_pow2(ids, seeds,
                                                            starts)
            stacked_starts = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *run_starts)
            try:
                stacked, sizes = self._local_train_cohort(
                    stacked_starts, run_ids, run_seeds)
                if len(run_ids) != n:
                    stacked = jax.tree_util.tree_map(
                        lambda l: l[:n], stacked)
                    sizes = sizes[:n]
                return stacked, sizes
            except NotImplementedError:
                self._can_cohort = False
                self.fallbacks.append("train_cohort")
        outs = [self.trainer.local_train(p0, c, rnd_seed=s)
                for p0, c, s in zip(starts, ids, seeds)]
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[p for p, _ in outs])
        sizes = np.asarray([s for _, s in outs], np.float32)
        return stacked, sizes

    def run_meta(self) -> dict:
        """What actually ran, for ``RunHistory.meta``: whether the
        Pallas merge ran in interpret mode (None without kernel agg)
        and which batched paths fell back to the per-client loop."""
        return {"kernel_interpret": (self.interpret if self.use_kernel_agg
                                     else None),
                "engine_fallbacks": list(self.fallbacks)}

    # -- aggregation ----------------------------------------------------
    def aggregate(self, stacked, weights):
        """Weighted average of the stacked cohort; zero-weight rows are
        masked stragglers and contribute nothing."""
        return weighted_average_stacked(
            stacked, weights, use_kernel=self.use_kernel_agg,
            interpret=self.interpret)

    def merge_staleness(self, params, stacked, alphas):
        """Fused staleness-weighted window merge (async runtime): the
        batched equivalent of folding ``staleness_merge`` over the
        stacked rows, one device reduction."""
        return staleness_weighted_merge(
            params, stacked, alphas, use_kernel=self.use_kernel_agg,
            interpret=self.interpret)

    def aggregate_or_keep(self, params, stacked, weights):
        """``aggregate`` with the all-masked guard on device: a
        ``lax.cond`` keeps ``params`` when every effective weight is
        zero, so the round never syncs a weight sum to the host."""
        return aggregate_or_keep(params, stacked, weights,
                                 use_kernel=self.use_kernel_agg,
                                 interpret=self.interpret)

    # -- fused round ----------------------------------------------------
    def train_round(self, params, client_ids: Sequence[int], rnd_seed: int,
                    weights: Optional[Sequence[float]] = None):
        """Train the cohort and aggregate the survivors.

        ``weights`` defaults to per-client sample counts; pass an
        explicit vector (zeros for masked clients) to drop updates
        without re-packing.  An empty cohort (all-straggler round)
        returns ``params`` unchanged — the FedDCT Alg. 2 convention —
        decided host-side BEFORE training; the all-masked (every
        survivor zero-weighted) guard lives on device.
        """
        tel = obs.TEL
        with tel.span("round.train", cohort=len(client_ids)):
            stacked, sizes = self.train_clients(params, client_ids,
                                                rnd_seed)
        if stacked is None:
            return params
        w = sizes if weights is None else np.asarray(  # fedlint: disable=FED002 -- weights is a host Sequence[float] from the caller, packing not a device readback
            weights, np.float32)
        with tel.span("round.aggregate", cohort=len(client_ids)):
            return self.aggregate_or_keep(params, stacked, w)

    # -- fused store-backed async window --------------------------------
    def train_window(self, store, params, client_ids: Sequence[int],
                     rnd_seeds: Sequence[int], alphas: Sequence[float]):
        """One drained async window against a ``ClientStateStore``:
        gather cohort snapshots -> cohort train -> folded staleness
        merge (zero-coefficient straggler/pad masking) -> scatter the
        new global row back into the merged clients' slots.

        The snapshot gather, the merge, the new-global flatten and the
        scatter each run as one device program per padded cohort-size
        bucket (the merge+scatter program donates the store buffers);
        padded rows ride through the merge with coefficient 0 instead
        of being sliced off, so there is no post-hoc host repack.  The
        merge dispatches the folded Pallas fedagg kernel when the
        engine was built with ``use_kernel_agg`` — the same program the
        dict path runs.
        Returns ``(new_params, new_global_flat)``.

        Row format is the STORE's concern: under ``quant_bits=8`` the
        gather dequantizes int8 rows into the cohort's f32 start
        params and the scatter re-quantizes the merged row per client
        (error-feedback residual folded in), so this window step is
        the per-window quantize -> store -> dequantize cycle without a
        single engine-side branch.
        """
        ids = [int(c) for c in client_ids]
        seeds = [int(s) for s in rnd_seeds]
        n = len(ids)
        if n == 0:
            return params, store.flatten(params)
        tel = obs.TEL
        coef = staleness_merge_coefficients(alphas)
        merge_kw = dict(use_kernel=self.use_kernel_agg,
                        interpret=self.interpret)
        # residency hook (duck-typed; dense stores don't have it): a
        # tiered store stages the whole window's rows in one batched
        # host->device promotion, so the looped fallback doesn't
        # promote one row per gather_one.
        stage = getattr(store, "ensure_window", None)
        if stage is not None:
            with tel.span("window.stage", cohort=n):
                stage(ids)
        if self._can_cohort:
            run_ids, run_seeds = self._pad_pow2(ids, seeds)
            with tel.span("window.gather", rows=len(run_ids)):
                starts = store.gather(run_ids)
            try:
                with tel.span("window.train", cohort=n,
                              padded=len(run_ids)):
                    stacked, _ = self._local_train_cohort(starts, run_ids,
                                                          run_seeds)
                flstats.record_update_norm(stacked, n)
                pad = np.zeros(len(run_ids) - n, np.float32)
                with tel.span("window.merge_scatter", rows=len(run_ids)):
                    return store.merge_scatter(
                        run_ids, stacked, np.concatenate([coef, pad]),
                        params, **merge_kw)
            except NotImplementedError:
                self._can_cohort = False
                self.fallbacks.append("train_window")
        # looped fallback (trainers without local_train_cohort): rows
        # still merge + scatter through the store's fused program.
        with tel.span("window.train", cohort=n, looped=True):
            outs = [self.trainer.local_train(store.gather_one(c), c,
                                             rnd_seed=s)
                    for c, s in zip(ids, seeds)]
        run_ids, trees = self._pad_pow2(ids, [p for p, _ in outs])
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *trees)
        pad = np.zeros(len(run_ids) - n, np.float32)
        with tel.span("window.merge_scatter", rows=len(run_ids)):
            return store.merge_scatter(run_ids, stacked,
                                       np.concatenate([coef, pad]), params,
                                       **merge_kw)


def make_engine(trainer, *, use_kernel_agg: bool = False,
                engine: str = "batched",
                interpret: Optional[bool] = None,
                mesh=None) -> BatchedClientEngine:
    """``engine``: "batched" (default) or "looped" (reference path for
    equivalence tests and A/B benchmarks).

    ``mesh``: a 1-D client mesh (``repro.distributed.make_client_mesh``)
    to shard cohorts across devices.  ``None`` or a single-device mesh
    selects the plain single-device engine — with one device the
    distributed path IS today's engine, so histories stay bit-identical
    by construction; a multi-device mesh returns the shard_map-backed
    ``ShardedClientEngine``.
    """
    if engine not in ("batched", "looped"):
        raise ValueError(f"unknown engine {engine!r}")
    if mesh is not None and int(mesh.size) > 1:
        if engine == "looped":
            raise ValueError("the looped reference engine cannot shard; "
                             "use engine='batched' with a client mesh")
        from repro.distributed.engine import ShardedClientEngine
        return ShardedClientEngine(trainer, mesh,
                                   use_kernel_agg=use_kernel_agg,
                                   interpret=interpret)
    return BatchedClientEngine(trainer, use_kernel_agg=use_kernel_agg,
                               interpret=interpret,
                               force_looped=(engine == "looped"))
