"""Client-sharded server aggregation: per-shard partial sums + one psum.

The single-device hot path (``weighted_average_stacked`` /
``fedagg_pytree``) reduces the whole flattened (N, P) update buffer on
one device.  Here each shard reduces only its own rows —
``sum_shard eff_c * u_c`` and ``sum_shard eff_c`` — and a single
``psum`` pair across the ``clients`` axis produces the global weighted
average.  That is the entire cross-device traffic of a round: one (P,)
all-reduce plus one scalar.

``use_kernel=True`` dispatches each shard's partial sum through the
Pallas ``fedagg_partial`` kernel instead of the jnp reduction — the
per-shard fedagg dispatch; the psum combine and the normalization are
unchanged, so the masking semantics are identical.

Numerics: identical masking semantics to the reference (rows with
``eff_c = w_c * alpha_c <= 0`` contribute exactly nothing; an
all-masked cohort yields zeros — or ``fallback`` when given), equal up
to float reassociation — partial sums reduce per-shard before the
psum, so results match the single-device reduction within dtype
tolerance, not bitwise.  ``sharded_staleness_merge`` rides the same
reduction with the PR 2 staleness coefficients, the global model as an
IMPLICIT row 0 (its telescoped coefficient multiplies the flattened
global row directly — no (K+1, ...) concatenated copy, matching the
folded single-device ``staleness_weighted_merge``).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.aggregation import staleness_merge_coefficients
from repro.distributed.plan import ClientShardingPlan
from repro.kernels.fedagg import fedagg_partial
from repro.kernels.ops import (flatten_updates, resolve_interpret,
                               unflatten_result)

# (mesh, kernel dispatch) -> jitted shard_map reduction (meshes hash by
# device assignment, so one compiled program per distinct client mesh
# and dispatch mode)
_AGG_CACHE: Dict[tuple, object] = {}
_MERGE_CACHE: Dict[tuple, object] = {}


def _resolve_kernel(use_kernel, interpret):
    """Normalize the dispatch key: the jnp path ignores ``interpret``;
    the kernel path resolves it (``resolve_interpret``)."""
    if not use_kernel:
        return False, None
    return True, resolve_interpret(interpret)


def _agg_fn(mesh, use_kernel: bool, interpret):
    key = (mesh, use_kernel, interpret)
    fn = _AGG_CACHE.get(key)
    if fn is None:
        axis = mesh.axis_names[0]

        def partial_reduce(u, w, a):
            # u (rows/D, P) f32, w/a (rows/D,): this shard's rows only.
            eff = w * a
            eff = jnp.where(eff > 0.0, eff, 0.0)
            # fused straggler/padding mask: a row with eff <= 0 is
            # zeroed BEFORE the reduction, so nonfinite garbage in
            # masked rows can never poison the average (the fedagg
            # kernel convention — the kernel fuses the same mask).
            if use_kernel:
                local = fedagg_partial(u, eff, interpret=interpret)
            else:
                masked = jnp.where((eff > 0.0)[:, None], u, 0.0)
                local = eff @ masked
            num = jax.lax.psum(local, axis)             # (P,)
            den = jax.lax.psum(eff.sum(), axis)         # scalar
            return num / jnp.maximum(den, 1e-30), den

        fn = jax.jit(jax.shard_map(
            partial_reduce, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis)), out_specs=(P(), P()),
            check_vma=False))
        _AGG_CACHE[key] = fn
    return fn


def sharded_aggregate(mesh, stacked, weights, *, alphas=None,
                      fallback=None, use_kernel: bool = False,
                      interpret=None):
    """Client-sharded ``weighted_average_stacked``.

    ``stacked`` is a pytree whose leaves carry a leading client axis
    (N, ...); ``weights`` (N,) and optional ``alphas`` (N,) multiply
    into per-row effective weights.  The buffer is flattened once into
    (N, P) f32 (cached unflatten spec — the fedagg pytree convention),
    zero-padded to a multiple of the mesh size with zero effective
    weight (exact no-op rows), reduced per shard — through the Pallas
    ``fedagg_partial`` kernel when ``use_kernel`` — and combined by one
    psum.  Returns the aggregated pytree with per-leaf shapes/dtypes
    restored.

    ``fallback``: an optional per-row-shaped pytree (the global params)
    returned — via a device-side select, no host sync — when every
    effective weight is zero (the all-masked round).
    """
    buf, treedef, spec = flatten_updates(stacked)
    n = buf.shape[0]
    w = jnp.asarray(weights, jnp.float32).reshape(-1)
    a = (jnp.ones_like(w) if alphas is None
         else jnp.asarray(alphas, jnp.float32).reshape(-1))
    if w.shape[0] != n or a.shape[0] != n:
        raise ValueError(
            f"weights/alphas length {w.shape[0]}/{a.shape[0]} != rows {n}")
    plan = ClientShardingPlan.for_cohort(n, mesh)
    use_kernel, interpret = _resolve_kernel(use_kernel, interpret)
    flat, den = _agg_fn(mesh, use_kernel, interpret)(
        plan.pad_stacked(buf, mode="zero"),
        plan.pad_weights(w), plan.pad_weights(a))
    out = unflatten_result(flat, treedef, spec)
    if fallback is None:
        return out
    return jax.tree_util.tree_map(
        lambda m, p: jnp.where(den > 0.0, m.astype(p.dtype), p),
        out, fallback)


def _merge_fn(mesh, use_kernel: bool, interpret):
    key = (mesh, use_kernel, interpret)
    fn = _MERGE_CACHE.get(key)
    if fn is None:
        axis = mesh.axis_names[0]

        def partial_merge(u, c):
            # u (rows/D, P) f32, c (rows/D,) this shard's (already
            # normalized) merge coefficients; zero rows are padding or
            # masked stragglers — exact no-ops.
            if use_kernel:
                local = fedagg_partial(u, c, interpret=interpret)
            else:
                masked = jnp.where((c > 0.0)[:, None], u, 0.0)
                local = c @ masked
            return jax.lax.psum(local, axis)            # (P,)

        fn = jax.jit(jax.shard_map(
            partial_merge, mesh=mesh,
            in_specs=(P(axis), P(axis)), out_specs=P(),
            check_vma=False))
        _MERGE_CACHE[key] = fn
    return fn


@jax.jit
def _fold_global(flat_sum, global_params, c0):
    # flatten of the global model rides inside the jit: one dispatch
    # per window, not one per leaf
    g_flat = jnp.concatenate(
        [l.reshape(-1).astype(jnp.float32)
         for l in jax.tree_util.tree_leaves(global_params)])
    g_term = jnp.where(c0 > 0.0, c0 * g_flat, 0.0)
    return g_term + flat_sum


def sharded_staleness_merge(mesh, global_params, stacked, alphas, *,
                            use_kernel: bool = False, interpret=None):
    """Client-sharded ``staleness_weighted_merge``: the async window
    merge as one sharded reduction over the client rows, the global
    model riding as an IMPLICIT row 0 — its telescoped coefficient
    multiplies the flattened global row directly instead of
    concatenating a (K+1, ...) copy through the mesh.  Zero-alpha rows
    (masked stragglers) contribute exactly nothing.  ``use_kernel``
    dispatches each shard's partial sum through the Pallas
    ``fedagg_partial`` kernel."""
    return sharded_fold_merge(mesh, global_params, stacked,
                              staleness_merge_coefficients(alphas),
                              use_kernel=use_kernel, interpret=interpret)


def sharded_fold_merge(mesh, global_params, stacked, coef, *,
                       use_kernel: bool = False, interpret=None):
    """``sharded_staleness_merge`` from the (K+1,) telescoped
    coefficients themselves (global row first; zero entries are
    padding or masked stragglers).  The row-sharded client-state
    store's kernel merge runs here: a Mosaic kernel cannot be
    partitioned by XLA, so on a multi-device mesh it must run per
    shard inside ``shard_map``."""
    coef = np.asarray(coef, np.float64)
    # normalize host-side (the coefficients are host scalars already):
    # entries sum to 1 up to fp, mirroring the reference's in-program
    # normalization within reassociation tolerance.
    c = np.where(coef > 0.0, coef, 0.0).astype(np.float64)
    c = (c / max(c.sum(), 1e-30)).astype(np.float32)
    buf, treedef, spec = flatten_updates(stacked)
    n = buf.shape[0]
    plan = ClientShardingPlan.for_cohort(n, mesh)
    use_kernel, interpret = _resolve_kernel(use_kernel, interpret)
    flat_sum = _merge_fn(mesh, use_kernel, interpret)(
        plan.pad_stacked(buf, mode="zero"), plan.pad_weights(c[1:]))
    flat = _fold_global(flat_sum, global_params, jnp.float32(c[0]))
    merged = unflatten_result(flat, treedef, spec)
    # unflatten_result restores the STACKED leaves' dtypes; re-cast to
    # the global model's per-leaf dtypes (identical trees in practice)
    return jax.tree_util.tree_map(
        lambda g, m: m.astype(g.dtype), global_params, merged)
