"""Client-sharded execution engine: ``shard_map`` cohorts over a mesh.

``BatchedClientEngine`` (PR 1) made a cohort ONE vmapped device
program; this subclass makes cohort size scale with device count
instead of device memory.  Each client shard's snapshots, data batches
and rng-derived streams land on their own device, local epochs run
under ``shard_map`` with ZERO cross-device collectives (the client axis
is embarrassingly parallel), and the merge reduces per-shard partial
sums into a single psum (``repro.distributed.aggregate``).

Trainers opt in through the ``wrap`` hook of ``local_train_batch`` /
``local_train_cohort``: the trainer hands its pure stacked-train
function (plus how many leading args are replicated) to the engine,
which returns the shard_map-wrapped runner.  Trainers without the hook
— or without the batched paths at all — transparently fall back to the
inherited single-device semantics, so every scheduler keeps working
unmodified.

Pallas kernel aggregation (``use_kernel_agg``) dispatches each shard's
partial sum through the ``fedagg_partial`` kernel inside the psum
reduction (``repro.distributed.aggregate``); the combine and
normalization are unchanged, so the flag changes how a shard reduces
its own rows, not the semantics.

Single-device note: ``make_engine(..., mesh=<1-device mesh>)``
deliberately returns the plain ``BatchedClientEngine`` — the
distributed path with one device IS the existing engine, bit-identical
by construction rather than by tolerance.
"""

from __future__ import annotations

import inspect
from typing import Callable, Dict, Optional

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.engine import BatchedClientEngine
from repro.distributed.aggregate import (sharded_aggregate,
                                         sharded_staleness_merge)
from repro.distributed.plan import ClientShardingPlan


def shard_cohort_train(mesh, train_fn: Callable, *,
                       replicated: int = 0) -> Callable:
    """Wrap a pure stacked-train function in a client-sharded runner.

    ``train_fn(*args)`` must treat its leading client axis elementwise
    (the engine contract: vmap over clients of a scan over local
    steps).  The first ``replicated`` positional args are broadcast to
    every device (the shared global params of the sync path); every
    remaining arg is a stacked pytree/array whose leading axis is
    sharded over the mesh's client axis.  Cohorts are padded to a
    multiple of the mesh size by repeating the last real row
    (deterministic duplicate work, sliced off again — real rows are
    unaffected because the axis is elementwise), so uneven cohorts and
    cohorts smaller than the mesh both work.

    The returned runner jits one shard_map program per argument arity;
    padded cohort shapes bound retraces exactly like the engine's pow2
    convention.
    """
    axis = mesh.axis_names[0]
    jitted: Dict[int, Callable] = {}

    def _build(nargs: int):
        in_specs = tuple([P()] * replicated
                         + [P(axis)] * (nargs - replicated))
        return jax.jit(jax.shard_map(train_fn, mesh=mesh,
                                     in_specs=in_specs, out_specs=P(axis),
                                     check_vma=False))

    def run(*args):
        sharded_args = args[replicated:]
        if not sharded_args:
            raise ValueError("shard_cohort_train needs at least one "
                             "sharded (per-client) argument")
        n = jax.tree_util.tree_leaves(sharded_args[0])[0].shape[0]
        plan = ClientShardingPlan.for_cohort(n, mesh)
        padded = tuple(plan.pad_stacked(a, mode="edge")
                       for a in sharded_args)
        fn = jitted.get(len(args))
        if fn is None:
            fn = jitted[len(args)] = _build(len(args))
        return plan.unpad(fn(*args[:replicated], *padded))

    # the replicated args' placement: a caller whose replicated arg is the
    # same on every call (a trainer's sample table) puts it there once,
    # so that no call copies it over the mesh again
    run.replicated_sharding = NamedSharding(mesh, P())
    return run


class ShardedClientEngine(BatchedClientEngine):
    """``BatchedClientEngine`` whose cohorts run under ``shard_map``
    over a 1-D client mesh and whose merges are sharded psum
    reductions.  One instance per (run, mesh)."""

    def __init__(self, trainer, mesh, *, interpret: Optional[bool] = None,
                 pad_cohorts: bool = True, **kw):
        super().__init__(trainer, interpret=interpret,
                         pad_cohorts=pad_cohorts, **kw)
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"client mesh must be 1-D, got axes {mesh.axis_names}")
        self.mesh = mesh
        self._wrapped: Dict[tuple, Callable] = {}
        self._accepts_wrap: Dict[str, bool] = {}

    # -- cohort padding: compose pow2 with the mesh multiple ------------
    def _pad_target(self, n: int) -> int:
        # lists padded here land on a multiple of the mesh size already,
        # so the per-bucket edge padding inside shard_cohort_train is a
        # no-op whenever the cohort is a single shape bucket.
        return ClientShardingPlan.for_cohort(n, self.mesh,
                                             pow2=True).padded_n

    # -- trainer hook ---------------------------------------------------
    def _wrap(self, train_fn: Callable, replicated: int) -> Callable:
        """The ``wrap`` hook handed to trainers: cache one sharded
        runner per (function, replicated-arity)."""
        key = (getattr(train_fn, "__func__", train_fn), int(replicated))
        fn = self._wrapped.get(key)
        if fn is None:
            fn = shard_cohort_train(self.mesh, train_fn,
                                    replicated=replicated)
            self._wrapped[key] = fn
        return fn

    def _trainer_takes_wrap(self, name: str) -> bool:
        ok = self._accepts_wrap.get(name)
        if ok is None:
            try:
                params = inspect.signature(
                    getattr(self.trainer, name)).parameters
                ok = "wrap" in params
            except (TypeError, ValueError):
                ok = False
            self._accepts_wrap[name] = ok
        return ok

    def _local_train_batch(self, params, ids, rnd_seed):
        if self._trainer_takes_wrap("local_train_batch"):
            return self.trainer.local_train_batch(params, ids, rnd_seed,
                                                  wrap=self._wrap)
        return super()._local_train_batch(params, ids, rnd_seed)

    def _local_train_cohort(self, stacked_starts, ids, seeds):
        if self._trainer_takes_wrap("local_train_cohort"):
            return self.trainer.local_train_cohort(stacked_starts, ids,
                                                   seeds, wrap=self._wrap)
        return super()._local_train_cohort(stacked_starts, ids, seeds)

    # -- aggregation: per-shard partial sums + one psum -----------------
    def aggregate(self, stacked, weights):
        return sharded_aggregate(self.mesh, stacked, weights,
                                 use_kernel=self.use_kernel_agg,
                                 interpret=self.interpret)

    def aggregate_or_keep(self, params, stacked, weights):
        # the all-masked guard rides the psum'd denominator: a
        # device-side select, no host sync (mirrors the base engine's
        # lax.cond guard).
        return sharded_aggregate(self.mesh, stacked, weights,
                                 fallback=params,
                                 use_kernel=self.use_kernel_agg,
                                 interpret=self.interpret)

    def merge_staleness(self, params, stacked, alphas):
        return sharded_staleness_merge(self.mesh, params, stacked, alphas,
                                       use_kernel=self.use_kernel_agg,
                                       interpret=self.interpret)
