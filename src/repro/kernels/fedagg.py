"""Weighted federated aggregation Pallas TPU kernels.

The FedDCT server's hot loop: w_global = sum_c (s_c / sum s) * w_c over
the stacked client updates (N_clients, P).  One pass over HBM, f32
accumulation in VMEM, parameter axis tiled so each (N, bp) panel fits
VMEM regardless of model size.  Weight normalization AND straggler
masking are fused: a zero-weight row (a dropped/straggling client) is
zeroed inside the kernel before the reduction, so non-finite garbage in
masked rows can never poison the average and the scheduler never has to
re-pack the stacked buffer after a drop.

The async runtime adds a per-row ``alphas`` vector (staleness merge
coefficients): the effective row weight is ``w_c * alpha_c``, so a
zero-alpha row (a fully-stale / masked client) is a straggler exactly
like a zero-weight row.  ``alphas=None`` keeps the original FedAvg
semantics (all ones).

Two companions serve the async-runtime merge paths:

* ``fedagg_fold`` — the folded-row-0 staleness window merge: client
  rows (K, P) plus the current global row (P,) and the telescoped
  coefficient vector (K+1,) with the global model as the IMPLICIT row
  0, so no (K+1, P) concatenated copy is ever materialized.
* ``fedagg_partial`` — the UNNORMALIZED masked row-sum
  ``sum_c c_c * u_c`` over one shard's rows: the per-shard term of the
  client-sharded psum reductions (``repro.distributed.aggregate``),
  same masking convention, normalization left to the caller's psum'd
  denominator.

All three run ONE kernel body: a masked multiply + row-axis sum (not a
dot), so appending zero-coefficient rows — the engine's padded cohort
buckets — appends exact +0.0 terms, a bitwise no-op.  The wrappers
mask and normalize the (K,) coefficient vector in XLA and hand the
kernel a 2-D ``(K, 1)`` column (TPU vector layouts are 2-D: a 1-D
coefficient block would need a 1-D -> 2-D relayout Mosaic refuses).
The parameter axis is tiled in lane-aligned ``block_p`` panels (a
multiple of 128, or the whole row); the last panel may run past P —
its out-of-range lanes are never written back — so the (K, P) buffer
is never padded or copied.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Bytes of ONE (K, block_p) f32 input panel.  Pallas double-buffers it
# and the masked product needs a panel-sized temporary, so about three
# of these must fit v5e's 16 MiB default scoped VMEM.
PANEL_BYTES = 4 * 1024 * 1024
MAX_BLOCK_P = 65536


def default_block_p(k: int) -> int:
    """Lane-aligned panel width for a K-row reduction: the widest
    multiple of 128 whose (K, block_p) f32 panel fits ``PANEL_BYTES``
    (rows counted padded to the 8-sublane tile), capped at
    ``MAX_BLOCK_P``."""
    rows = max(8, -(-int(k) // 8) * 8)
    bp = (PANEL_BYTES // (4 * rows)) // LANES * LANES
    return int(min(max(bp, LANES), MAX_BLOCK_P))


def _masked_rowsum(u_ref, c_ref):
    # u (K, bp), c (K, 1) masked coefficients.  Zero-coefficient rows
    # contribute exactly nothing even when their row is inf/nan; the
    # row-axis sum (not a dot) keeps padded rows bitwise no-ops.
    u = u_ref[...].astype(jnp.float32)
    c = c_ref[...]
    u = jnp.where(c > 0.0, u, 0.0)
    return jnp.sum(u * c, axis=0, keepdims=True)    # (1, bp)


def _rowsum_kernel(u_ref, c_ref, o_ref):
    o_ref[...] = _masked_rowsum(u_ref, c_ref).astype(o_ref.dtype)


def _fold_kernel(u_ref, c_ref, g_ref, c0_ref, o_ref):
    c0 = c0_ref[...]                                # (1, 1)
    gt = g_ref[...].astype(jnp.float32)             # (1, bp)
    g_term = jnp.where(c0 > 0.0, c0 * gt, 0.0)
    o_ref[...] = (g_term + _masked_rowsum(u_ref, c_ref)).astype(o_ref.dtype)


def _panel_width(k: int, p: int, block_p) -> int:
    bp = default_block_p(k) if block_p is None else int(block_p)
    if bp % LANES:
        raise ValueError(f"block_p must be a multiple of {LANES}, got {bp}")
    return p if p <= bp else bp


def _rowsum_call(updates, c, g=None, c0=None, *, block_p, interpret):
    """One pallas_call of the shared body over (K, P) ``updates`` and
    (K,) masked coefficients ``c``; with ``g``/``c0`` the global row is
    folded in as row 0.  -> (P,) in ``updates.dtype``."""
    n, p = updates.shape
    bp = _panel_width(n, p, block_p)
    panel = lambda i: (0, i)
    in_specs = [pl.BlockSpec((n, bp), panel),
                pl.BlockSpec((n, 1), lambda i: (0, 0))]
    args = [updates, c.reshape(n, 1)]
    kernel = _rowsum_kernel
    if g is not None:
        in_specs += [pl.BlockSpec((1, bp), panel),
                     pl.BlockSpec((1, 1), lambda i: (0, 0))]
        args += [g.reshape(1, p), c0.reshape(1, 1)]
        kernel = _fold_kernel
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(p, bp),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bp), panel),
        out_shape=jax.ShapeDtypeStruct((1, p), updates.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*args)
    return out.reshape(p)


def _normalized(c):
    c = jnp.where(c > 0.0, c, 0.0)
    return c / jnp.maximum(c.sum(), 1e-30)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def _fedagg_call(updates, weights, alphas, block_p, interpret):
    c = _normalized(weights.astype(jnp.float32) * alphas.astype(jnp.float32))
    return _rowsum_call(updates, c, block_p=block_p, interpret=interpret)


def fedagg(updates, weights, *, alphas=None, block_p=None,
           interpret: bool = False):
    """updates (N,P), weights (N,) -> weighted average (P,).

    ``sum_c eff_c * u_c / sum(eff)`` with ``eff_c = w_c * alpha_c``
    (``alphas=None`` -> all ones).  Rows with ``eff_c <= 0`` are masked
    out (see module docstring); if every effective weight is zero the
    result is all-zeros.  ``block_p=None`` sizes the panel from N
    (``default_block_p``).
    """
    weights = jnp.asarray(weights, jnp.float32)
    if alphas is None:
        alphas = jnp.ones_like(weights)
    return _fedagg_call(updates, weights, jnp.asarray(alphas, jnp.float32),
                        block_p, interpret)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def _fedagg_fold_call(updates, g, coef, block_p, interpret):
    c = _normalized(coef)
    return _rowsum_call(updates, c[1:], g, c[0], block_p=block_p,
                        interpret=interpret)


def fedagg_fold(updates, g, coef, *, block_p=None, interpret: bool = False):
    """Folded staleness window merge: updates (K,P), global row g (P,),
    coef (K+1,) -> merged row (P,).

    ``coef`` is ``staleness_merge_coefficients(alphas)`` order: the
    global model's telescoped coefficient first, then one entry per
    client row.  Coefficients are masked at <= 0 and renormalized
    (the fedagg convention), so masked stragglers and padded rows
    contribute exactly nothing; if every coefficient is zero the
    result is all-zeros.
    """
    return _fedagg_fold_call(updates, g, jnp.asarray(coef, jnp.float32),
                             block_p, interpret)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def _fedagg_partial_call(updates, coef, block_p, interpret):
    c = jnp.where(coef > 0.0, coef, 0.0)
    return _rowsum_call(updates, c, block_p=block_p, interpret=interpret)


def fedagg_partial(updates, coef, *, block_p=None, interpret: bool = False):
    """UNNORMALIZED masked weighted row-sum ``sum_c c_c * u_c`` -> (P,).

    The per-shard term of the client-sharded psum reductions: rows with
    ``c_c <= 0`` are zeroed before the sum (straggler/padding mask),
    normalization is the caller's job (divide by the psum'd coefficient
    sum).  Runs per shard inside ``shard_map``.
    """
    return _fedagg_partial_call(updates, jnp.asarray(coef, jnp.float32),
                                block_p, interpret)
