"""jit'd public wrappers around the Pallas kernels.

``interpret=None`` resolves through ``resolve_interpret``: the Pallas
interpreter on a CPU backend (the test suite), the compiled kernel on
every other backend — a chip run never falls back to the interpreter.
``gqa_flash_attention`` adapts the model zoo's (B,S,H,D)/(B,T,Hkv,D)
layout to the kernel's folded-head layout.

``fedagg_pytree`` is the pytree-native server aggregation hot path: the
stacked client-update pytree is flattened ONCE into a single (N, P)
f32 buffer (unflatten spec cached per tree structure), reduced by the
fused fedagg kernel in one pass, and split back — instead of one kernel
launch per leaf.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fedagg import fedagg, fedagg_fold, fedagg_partial
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssm_scan import ssm_scan


def resolve_interpret(interpret=None) -> bool:
    """The caller's explicit choice, else interpret mode exactly when
    the default backend is the CPU."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def gqa_flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                        block_q=128, block_k=128, interpret=None):
    """q (B,S,H,D); k/v (B,T,Hkv,D) -> (B,S,H,D)."""
    interpret = resolve_interpret(interpret)
    b, s, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    kx = jnp.repeat(k, rep, axis=2)
    vx = jnp.repeat(v, rep, axis=2)
    fold = lambda t: jnp.moveaxis(t, 2, 1).reshape(b * h, t.shape[1], d)
    o = flash_attention(fold(q), fold(kx), fold(vx), causal=causal,
                        window=window, q_offset=q_offset, block_q=block_q,
                        block_k=block_k, interpret=interpret)
    return jnp.moveaxis(o.reshape(b, h, s, d), 1, 2)


def ssm_scan_op(x, dt, b_in, c_out, a_log, *, chunk=128, block_d=256,
                interpret=None):
    interpret = resolve_interpret(interpret)
    return ssm_scan(x, dt, b_in, c_out, a_log, chunk=chunk, block_d=block_d,
                    interpret=interpret)


def fedagg_op(updates, weights, *, alphas=None, block_p=None,
              interpret=None):
    interpret = resolve_interpret(interpret)
    return fedagg(updates, weights, alphas=alphas, block_p=block_p,
                  interpret=interpret)


# ---------------------------------------------------------------------------
# Pytree-native aggregation: flatten once, one kernel pass, cached spec
# ---------------------------------------------------------------------------

# treedef + leaf (shape, dtype) signature -> list of (offset, size, shape,
# dtype) describing how to slice the flat (P,) result back into leaves.
_UNFLATTEN_SPECS: Dict[tuple, List[Tuple[int, int, tuple, object]]] = {}


def _unflatten_spec(treedef, leaves):
    key = (treedef, tuple((l.shape, str(l.dtype)) for l in leaves))
    spec = _UNFLATTEN_SPECS.get(key)
    if spec is None:
        spec, off = [], 0
        for l in leaves:
            size = int(np.prod(l.shape[1:], dtype=np.int64)) if l.ndim > 1 \
                else 1
            spec.append((off, size, l.shape[1:], l.dtype))
            off += size
        _UNFLATTEN_SPECS[key] = spec
    return spec


def flatten_updates(stacked):
    """Stacked pytree (leaves (N, ...)) -> ((N, P) f32 buffer, treedef,
    unflatten spec).  The spec is cached per (structure, shapes, dtypes)
    so repeated rounds pay only for the concat itself."""
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    if not leaves:
        raise ValueError("empty pytree: nothing to aggregate")
    spec = _unflatten_spec(treedef, leaves)
    n = leaves[0].shape[0]
    buf = jnp.concatenate(
        [l.reshape(n, -1).astype(jnp.float32) for l in leaves], axis=1)
    return buf, treedef, spec


def unflatten_result(flat, treedef, spec):
    """(P,) flat aggregate -> pytree with per-leaf shapes/dtypes restored."""
    outs = [flat[off:off + size].reshape(shape).astype(dtype)
            for off, size, shape, dtype in spec]
    return jax.tree_util.tree_unflatten(treedef, outs)


# Unstacked variant: a single model pytree <-> one flat (P,) f32 row —
# the ``ClientStateStore`` convention (a client snapshot is one row of
# the (N, P) store buffer).  Spec cache shared-format with the stacked
# path: (offset, size, full leaf shape, dtype).
_TREE_SPECS: Dict[tuple, List[Tuple[int, int, tuple, object]]] = {}


def tree_spec(tree):
    """-> (treedef, [(offset, size, shape, dtype)], total P) for an
    UNSTACKED pytree (no leading client axis).  Cached per structure."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        raise ValueError("empty pytree: nothing to flatten")
    key = (treedef, tuple((tuple(l.shape), str(jnp.asarray(l).dtype))
                          for l in leaves))
    cached = _TREE_SPECS.get(key)
    if cached is None:
        spec, off = [], 0
        for l in leaves:
            size = int(np.prod(np.shape(l), dtype=np.int64))
            spec.append((off, size, tuple(np.shape(l)),
                         jnp.asarray(l).dtype))
            off += size
        cached = (spec, off)
        _TREE_SPECS[key] = cached
    spec, total = cached
    return treedef, spec, total


def fedagg_pytree(stacked_updates, weights, *, alphas=None, block_p=None,
                  interpret=None):
    """Weighted-average a pytree whose leaves are stacked (N, ...).

    Zero-weight rows (masked stragglers) contribute exactly nothing —
    the mask is fused into the kernel, so callers can keep dropped
    clients in the stacked buffer instead of re-packing it.  ``alphas``
    adds per-row staleness coefficients (effective weight
    ``w_c * alpha_c``); a zero-alpha row is masked like a zero weight.
    """
    interpret = resolve_interpret(interpret)
    buf, treedef, spec = flatten_updates(stacked_updates)
    flat = fedagg(buf, weights, alphas=alphas, block_p=block_p,
                  interpret=interpret)
    return unflatten_result(flat, treedef, spec)


def flatten_params_row(params):
    """Model pytree -> (P,) f32 row in ``flatten_updates`` leaf order
    (no leading client axis) — the global-row companion of the stacked
    (N, P) buffer.  Kept jit-traceable (callers fuse it into their own
    programs)."""
    return jnp.concatenate(
        [jnp.asarray(l).reshape(-1).astype(jnp.float32)
         for l in jax.tree_util.tree_leaves(params)])


# ---------------------------------------------------------------------------
# Quantized row views: shifted-scale int8 segments with fused scales
# ---------------------------------------------------------------------------

# int8 grid radius and range divisor.  253 steps (not 254) leave half a
# step of slack on each side of the value range, so snapping the
# zero-point onto the quantization grid can never push a rounded index
# past +/-127 — the round-trip error bound |x - dq(q(x))| <= scale/2
# holds without the clip ever truncating an in-range value.
QUANT_QMAX = 127.0
QUANT_STEPS = 253.0


def quantize_rows(frows, segs):
    """f32 rows -> (int8 rows, per-segment scale/zero-point meta).

    ``frows`` is (..., Pf) f32; ``segs`` is a static tuple of
    ``(offset, size)`` float-segment views covering the row (the
    store's per-leaf layout).  Returns ``(qrows (..., Pf) int8,
    meta (..., 2L) f32)`` with ``meta[..., j]`` = scale and
    ``meta[..., L+j]`` = the SNAP INDEX of segment ``j`` — the
    zero-point expressed in grid steps (``zp = scale * snap``).

    Shifted-scale scheme, per (row, segment): ``scale = range/253``
    and the zero-point is the range midpoint snapped onto the
    quantization grid (``snap = round(mid/scale)``).  Dequantization
    is ``(q + snap) * scale``: storing the snap index rather than the
    zero-point keeps that an add FEEDING a multiply — not the
    ``a*b + c`` shape XLA contracts into an FMA (it fuses straight
    through ``optimization_barrier`` on CPU) — so dequantized bits are
    identical across compilation units and exactly match the numpy
    oracle, and exact zeros round-trip exactly on every backend
    (``q + snap == 0 -> 0 * scale == 0``; 0 in [lo, hi] bounds
    ``|snap| <= 126``, inside the clip range).  Constant segments
    (range 0) take scale=1, snap=value — an exact round-trip.  Every
    reduction here is a per-segment min/max (order-independent), so
    quantized bits are identical across batch shapes — the property
    that keeps dense-quant and tiered-quant histories bit-identical.
    """
    qs, scales, snaps = [], [], []
    for off, size in segs:
        x = frows[..., off:off + size]
        lo, hi = x.min(axis=-1), x.max(axis=-1)
        rng = hi - lo
        flat0 = rng <= 0.0
        # explicit reciprocal multiply: XLA strength-reduces division
        # by a constant to exactly this, so spelling it out pins the
        # f32 semantics across backends AND keeps the numpy oracle
        # (ref.quantize_rows_ref) bit-exact without mimicking an
        # optimizer pass
        scale = jnp.where(flat0, jnp.float32(1.0),
                          rng * jnp.float32(1.0 / QUANT_STEPS))
        snap = jnp.where(flat0, lo,
                         jnp.round((lo + hi) / (2.0 * scale)))
        zp = scale * snap
        q = jnp.clip(jnp.round((x - zp[..., None]) / scale[..., None]),
                     -QUANT_QMAX, QUANT_QMAX).astype(jnp.int8)
        qs.append(q)
        scales.append(scale)
        snaps.append(snap)
    qrows = jnp.concatenate(qs, axis=-1)
    meta = jnp.stack(scales + snaps, axis=-1)
    return qrows, meta


def dequantize_rows(qrows, meta, segs):
    """Inverse row view of ``quantize_rows``: (..., Pf) int8 rows plus
    (..., 2L) scale/snap meta -> (..., Pf) f32 rows.  Pure elementwise
    ``(q + snap) * scale`` per segment — an add feeding a multiply has
    no FMA contraction to vary by compilation unit, so the bits are
    stable across batch shapes, programs and the numpy oracle (see
    ``quantize_rows``)."""
    n = len(segs)
    outs = []
    for j, (off, size) in enumerate(segs):
        q = qrows[..., off:off + size].astype(jnp.float32)
        outs.append((q + meta[..., n + j, None]) * meta[..., j, None])
    return jnp.concatenate(outs, axis=-1)


def dequantize_segment(qrows, meta, segs, j):
    """One segment's dequantized f32 view (``segs[j]`` of ``qrows``) —
    the per-leaf form the store's fused gather slices directly into
    leaf shapes, skipping the full-row concat."""
    off, size = segs[j]
    q = qrows[..., off:off + size].astype(jnp.float32)
    return (q + meta[..., len(segs) + j, None]) * meta[..., j, None]


def fedagg_fold_op(updates, g, coef, *, block_p=None, interpret=None):
    interpret = resolve_interpret(interpret)
    return fedagg_fold(updates, g, coef, block_p=block_p,
                       interpret=interpret)


def fedagg_partial_op(updates, coef, *, block_p=None, interpret=None):
    interpret = resolve_interpret(interpret)
    return fedagg_partial(updates, coef, block_p=block_p,
                          interpret=interpret)


def fedagg_fold_pytree(global_params, stacked_updates, coef, *,
                       block_p=None, interpret=None):
    """Folded staleness window merge over pytrees: ONE kernel pass on
    the flattened (K, P) client-row buffer with the global model as the
    IMPLICIT row 0 (its (P,) row rides in directly — no (K+1, ...)
    concatenated copy).

    This is the SHARED merge program of the async runtime's kernel
    path: both the dict-of-pytrees reference and the store-backed fused
    window step call it on identically-flattened buffers, which is what
    makes their histories bit-identical.  ``coef`` is the (K+1,)
    ``staleness_merge_coefficients`` vector (global first); padded /
    masked rows carry coefficient 0 and contribute exactly nothing.
    """
    interpret = resolve_interpret(interpret)
    buf, treedef, spec = flatten_updates(stacked_updates)
    g_flat = flatten_params_row(global_params)
    flat = fedagg_fold(buf, g_flat, coef, block_p=block_p,
                       interpret=interpret)
    out = unflatten_result(flat, treedef, spec)
    return jax.tree_util.tree_map(
        lambda g, m: m.astype(g.dtype), global_params, out)
