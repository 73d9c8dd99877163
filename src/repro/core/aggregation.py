"""Server-side model aggregation (Alg. 1 line 8 / Alg. 2 last line).

Three layers:

* ``weighted_average_stacked`` — the engine hot path.  Takes a pytree
  whose leaves already carry a leading client axis (N, ...) plus a
  weight vector (N,), and reduces on device.  Zero-weight rows are
  masked out (fused straggler masking), so dropped clients never force
  a host-side re-pack of the buffer.  An optional per-row ``alphas``
  vector multiplies the weights (staleness discounting for the async
  runtime); a zero-alpha row is masked exactly like a zero weight.
  ``use_kernel=True`` routes through the pytree-native Pallas fedagg
  path (single flattened (N, P) kernel pass); otherwise a pure-jnp
  einsum-style reduction.
* ``staleness_weighted_merge`` — the async runtime's windowed merge:
  the exact batched equivalent of sequentially applying
  ``staleness_merge`` row by row, computed as ONE stacked reduction
  with the global model as an IMPLICIT row 0 (its telescoped
  coefficient multiplies the global leaves directly — no
  ``jnp.concatenate`` of a (K+1, ...) copy, no fresh ``np.ones``
  weight vector per window).
* ``aggregate_or_keep`` — ``weighted_average_stacked`` with the
  all-masked guard moved on device: a ``lax.cond`` returns the global
  params unchanged when every effective weight is zero, so the round
  step never syncs a weight sum back to the host.
* ``weighted_average`` — list-of-pytrees convenience wrapper kept for
  the looped reference implementations and external callers; it stacks
  then delegates.

``staleness_merge`` is FedAsync's two-model blend (the one-client
degenerate case of ``staleness_weighted_merge``).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _agg_jnp(stacked, w, a):
    eff = w * a
    wn = jnp.where(eff > 0.0, eff, 0.0)
    wn = wn / jnp.maximum(wn.sum(), 1e-30)

    def agg(leaf):
        wb = wn.reshape((-1,) + (1,) * (leaf.ndim - 1))
        u = jnp.where(wb > 0.0, leaf.astype(jnp.float32), 0.0)
        return jnp.sum(u * wb, axis=0).astype(leaf.dtype)
    return jax.tree_util.tree_map(agg, stacked)


def weighted_average_stacked(stacked, weights, *, alphas=None,
                             use_kernel: bool = False,
                             interpret: Optional[bool] = None):
    """Reduce a stacked update pytree (leaves (N, ...)) with weights (N,).

    sum_c eff_c * u_c / sum(eff) with eff_c = w_c * alpha_c
    (``alphas=None`` -> all ones).  Rows with eff_c <= 0 are masked to
    exactly zero before the reduction (straggler masking); if every
    effective weight is zero the result is an all-zeros pytree.
    """
    w = jnp.asarray(weights, jnp.float32)
    if use_kernel:
        from repro.kernels import fedagg_pytree
        a = None if alphas is None else jnp.asarray(alphas, jnp.float32)
        return fedagg_pytree(stacked, w, alphas=a, interpret=interpret)
    a = (jnp.ones_like(w) if alphas is None
         else jnp.asarray(alphas, jnp.float32))
    return _agg_jnp(stacked, w, a)


@jax.jit
def _agg_or_keep_jnp(params, stacked, w, a):
    eff = w * a
    total = jnp.sum(jnp.where(eff > 0.0, eff, 0.0))

    def agg():
        # cast to the params leaves' dtypes so both cond branches carry
        # identical avals even when a trainer returns float-promoted
        # updates (astype is a no-op for matching dtypes)
        return jax.tree_util.tree_map(
            lambda p, m: m.astype(p.dtype), params,
            _agg_jnp(stacked, w, a))

    return jax.lax.cond(total > 0.0, agg, lambda: params)


def aggregate_or_keep(params, stacked, weights, *, alphas=None,
                      use_kernel: bool = False,
                      interpret: Optional[bool] = None):
    """``weighted_average_stacked`` that falls back to ``params`` when
    every effective weight is zero (the all-straggler round), decided
    ON DEVICE via ``lax.cond`` — no per-round host sync of the weight
    sum.  Leaf shapes/dtypes of ``params`` must match the per-row
    shapes of ``stacked`` (the engine round contract)."""
    w = jnp.asarray(weights, jnp.float32)
    a = (jnp.ones_like(w) if alphas is None
         else jnp.asarray(alphas, jnp.float32))
    if use_kernel:
        agg = weighted_average_stacked(stacked, w, alphas=a,
                                       use_kernel=True, interpret=interpret)
        any_live = jnp.sum(jnp.where(w * a > 0.0, w * a, 0.0)) > 0.0
        return jax.tree_util.tree_map(
            lambda p, m: jnp.where(any_live, m.astype(p.dtype), p),
            params, agg)
    return _agg_or_keep_jnp(params, stacked, w, a)


def weighted_average(param_list: Sequence, sizes: Sequence[float],
                     use_kernel: bool = False,
                     interpret: Optional[bool] = None):
    """FedAvg: sum_c w_c * s_c / sum(s) over a list of update pytrees."""
    if len(param_list) == 0:
        raise ValueError("no client updates to aggregate")
    w = jnp.asarray(np.asarray(sizes, np.float32))
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *param_list)
    return weighted_average_stacked(stacked, w, use_kernel=use_kernel,
                                    interpret=interpret)


def staleness_merge(global_params, client_params, alpha_t: float):
    """FedAsync: w <- (1-a) w + a w_c."""
    return jax.tree_util.tree_map(
        lambda g, c: ((1 - alpha_t) * g.astype(jnp.float32)
                      + alpha_t * c.astype(jnp.float32)).astype(g.dtype),
        global_params, client_params)


def staleness_merge_coefficients(alphas) -> np.ndarray:
    """Row coefficients of the fused window merge.

    Sequentially applying ``staleness_merge`` with alphas a_1..a_K
    (row order = merge order) telescopes to the convex combination

        w <- prod_i (1-a_i) * w  +  sum_i a_i * prod_{j>i} (1-a_j) * w_i

    Returns the (K+1,) coefficient vector [global, row_1..row_K]; the
    entries sum to exactly 1 (up to fp), so the normalized stacked
    reduction reproduces the sequential merge in one pass.
    """
    a = np.asarray(alphas, np.float64).reshape(-1)
    one_minus = 1.0 - a
    # suffix[i] = prod_{j>i} (1-a_j); suffix[K-1] = 1
    suffix = np.ones_like(a)
    if a.size > 1:
        suffix[:-1] = np.cumprod(one_minus[::-1])[::-1][1:]
    coef = a * suffix
    g = float(np.prod(one_minus)) if a.size else 1.0
    return np.concatenate([[g], coef]).astype(np.float32)


@jax.jit
def _merge_folded_jnp(global_params, stacked, coef):
    """Folded window merge: coef (K+1,) row coefficients with the
    global model as the IMPLICIT row 0.  The exact per-leaf ops of
    ``_agg_jnp`` with the row-0 term pulled out of the stacked
    reduction — zero-coefficient rows are masked to exactly zero
    BEFORE the sum, so nonfinite garbage in masked rows (and the
    zero-padded rows of the store's fused round step) contributes
    nothing."""
    c = jnp.where(coef > 0.0, coef, 0.0)
    c = c / jnp.maximum(c.sum(), 1e-30)
    cr = c[1:]

    def merge(g, leaf):
        cb = cr.reshape((-1,) + (1,) * (leaf.ndim - 1))
        u = jnp.where(cb > 0.0, leaf.astype(jnp.float32), 0.0)
        g_term = jnp.where(c[0] > 0.0,
                           c[0] * g.astype(jnp.float32), 0.0)
        return (g_term + jnp.sum(u * cb, axis=0)).astype(g.dtype)

    return jax.tree_util.tree_map(merge, global_params, stacked)


def staleness_weighted_merge(global_params, stacked, alphas, *,
                             use_kernel: bool = False,
                             interpret: Optional[bool] = None):
    """Merge a whole aggregation window into the global model at once.

    ``stacked`` holds the window's client models with a leading row axis
    (K, ...); ``alphas`` are the per-row staleness weights
    a_i = alpha * (s_i + 1)^-a in merge order.  The result is the same
    convex combination a sequential ``staleness_merge`` fold would
    produce (up to float reassociation), computed as ONE stacked
    reduction with the global model as an IMPLICIT row 0: its
    telescoped coefficient multiplies the global leaves directly, so
    no (K+1, ...) copy is materialized and no per-window ``np.ones``
    weight vector is allocated.  Zero-alpha rows (masked stragglers)
    contribute exactly nothing.

    ``use_kernel=True`` routes through the folded Pallas fedagg kernel
    (``fedagg_fold_pytree``): the same implicit-row-0 formulation on
    the flattened (K, P) buffer — no (K+1, ...) concatenated copy
    there either.  The store-backed fused window step dispatches the SAME
    program on the same flattened buffer, so kernel-path histories are
    bit-identical between the dict and store snapshot paths.
    """
    coef = staleness_merge_coefficients(alphas)
    if use_kernel:
        from repro.kernels import fedagg_fold_pytree
        return fedagg_fold_pytree(global_params, stacked,
                                  jnp.asarray(coef), interpret=interpret)
    return _merge_folded_jnp(global_params, stacked, jnp.asarray(coef))
