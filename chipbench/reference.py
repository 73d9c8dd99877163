"""Plain reference of one FedDCT round, and the numbers that decide
``correct``.

Nothing here imports the program.  The reference is given what the
window's round took in: the global model entering the round, the ids,
data-stream seeds and start models of the clients it trained, and the
federation's hyper-parameters; each client's data comes from the
benchmark's own copy (the configuration's module under
``chipbench/datasets/``).  From those it trains each client by plain
Adam on the model module's ``loss``, in float32 at the matmul precision
the configuration states (``default``: one bfloat16 pass on a TPU, as
the program runs; 120 Adam steps amplify any difference about
10^4-fold, so a reference at ``highest`` could not tell a fault from
rounding), and merges the trained rows as the traffic's ``merge``
names it:

* ``sync_mean``: FedDCT's sync merge, the sample-weighted mean of the
  survivors' models;
* ``staleness_fold``: the semi-async merge, applied row by row in merge
  order in float64, ``g <- (1 - a_i) g + a_i x_i`` with
  ``a_i = alpha (s_i + 1)^-a`` (``poly``) or ``alpha`` (``constant``).

The numbers compared (each against its limit in ``cells/<cell>.json``):

* ``train_gap_median``: for each compared client, the worst leaf's gap
  between the norm of the program's change of the model and the norm
  of the reference's, over the larger of the reference's change of that
  leaf and of the median leaf; the median over the compared clients.
  Leaves whose first-step gradient in the reference is under a
  thousandth of the median leaf's are left out, and so are clients
  whose first-step gradient is zero on every leaf: their start model
  fits the batch exactly in f32, and both sides train it to no change.
  Early in a run the 120 steps of a client amplify rounding apart on
  some clients, so the worst client swings from seed to seed and the
  median over clients does not (``PERF.md`` section 4 gives the
  readings).
* ``merge_gap``: the worst leaf's largest difference between the
  program's merged global and the reference's merge of the program's
  trained rows, over the larger of that leaf's and the median leaf's
  largest reference value.
* ``data_gap``: the clients whose data the program's trainer holds
  otherwise than the benchmark's copy.  Exact: limit 0.
* ``start_gap`` (traffic that trains clients from a snapshot taken at
  their selection): the merged clients whose start model is not
  bitwise the global model at the boundary of the round that selected
  them.  Exact: limit 0.
"""

from __future__ import annotations

import importlib
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def precision_of(name: str):
    """A configuration's stated matmul precision (``default``, ``high``,
    ``highest``) as a ``lax.Precision``."""
    return {"default": lax.Precision.DEFAULT, "high": lax.Precision.HIGH,
            "highest": HIGHEST}[name]
EXCLUDE_GRAD_SHARE = 1e-3


def model_module(name: str):
    return importlib.import_module(f"chipbench.models.{name}")


def make_train(model, sizes: dict, lr: float, dtype=jnp.float32,
               precision=HIGHEST, half_batch: bool = False):
    """Jitted Adam over a client's batches, vmapped over clients:
    (start params (C, ...), xs (C, T, B, ...), ys (C, T, B)) -> trained
    params (C, ...) in float32.  Every array of the computation is in
    ``dtype``.  ``half_batch`` trains on the first half of each batch
    (a planted fault, for calibration)."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    grad = jax.grad(lambda p, x, y: model.loss(sizes, p, x, y, precision))

    def one(p0, xs, ys):
        p = jax.tree_util.tree_map(lambda a: a.astype(dtype), p0)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
        xs = xs.astype(dtype)
        if half_batch:
            xs, ys = xs[:, :xs.shape[1] // 2], ys[:, :ys.shape[1] // 2]

        def step(carry, xy):
            p, m, v, t = carry
            g = grad(p, xy[0], xy[1])
            t = t + 1
            m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_,
                                       m, g)
            v = jax.tree_util.tree_map(
                lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
            # bias corrections as float32 scalars, then in ``dtype``: 1 -
            # 0.999 ** t is not representable in bfloat16 arithmetic
            tf = t.astype(jnp.float32)
            bc1 = (1 - jnp.float32(b1) ** tf).astype(dtype)
            bc2 = (1 - jnp.float32(b2) ** tf).astype(dtype)
            p = jax.tree_util.tree_map(
                lambda p_, m_, v_: p_ - lr * (m_ / bc1)
                / (jnp.sqrt(v_ / bc2) + eps), p, m, v)
            return (p, m, v, t), None

        (p, _, _, _), _ = lax.scan(step, (p, zeros, zeros,
                                          jnp.zeros((), jnp.int32)),
                                   (xs, ys))
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)

    return jax.jit(jax.vmap(one))


def make_grad_norms(model, sizes: dict):
    """Jitted per-leaf norms of the reference's first-step gradient."""
    grad = jax.grad(lambda p, x, y: model.loss(sizes, p, x, y, HIGHEST))

    def fn(p, x, y):
        g = grad(p, x, y)
        return [jnp.sqrt(jnp.sum(l * l)) for l in jax.tree_util.tree_leaves(g)]
    return jax.jit(fn)


def leaves64(tree) -> List[np.ndarray]:
    return [np.asarray(l, np.float64) for l in jax.tree_util.tree_leaves(tree)]


def row_of(stacked, i: int) -> List[np.ndarray]:
    """Row ``i`` of a stacked pytree as float64 leaves."""
    return [np.asarray(l[i], np.float64)
            for l in jax.tree_util.tree_leaves(stacked)]


def kept_leaves(grad_norms: Sequence[float]) -> List[bool]:
    """Leaves whose first-step reference gradient is at least a
    thousandth of the median leaf's (and not zero)."""
    g = np.asarray(grad_norms, np.float64)
    return list((g >= EXCLUDE_GRAD_SHARE * np.median(g)) & (g > 0))


def _ratio(num: float, den: float) -> float:
    return 0.0 if num == 0 else num / max(den, 1e-30)


def train_gap(start, prog, ref, keep: Sequence[bool]) -> tuple:
    """-> (worst kept leaf's norm-of-change gap (module docstring), the
    index of that leaf)."""
    d_ref = [np.linalg.norm(r - s) for s, r in zip(start, ref)]
    d_prog = [np.linalg.norm(p - s) for s, p in zip(start, prog)]
    kept = [(j, dp, dr) for j, (dp, dr, k) in
            enumerate(zip(d_prog, d_ref, keep)) if k]
    if not kept:
        return 0.0, -1
    floor = float(np.median([dr for _, _, dr in kept]))
    return max((_ratio(abs(dp - dr), max(dr, floor)), j)
               for j, dp, dr in kept)


def merge_gap(prog, ref) -> float:
    """Worst leaf's largest difference over its scale (module docstring)."""
    scale = [float(np.max(np.abs(r))) for r in ref]
    floor = float(np.median(scale))
    return max(_ratio(float(np.max(np.abs(p - r))), max(s, floor))
               for p, r, s in zip(prog, ref, scale))


def sync_merge(rows: Sequence[List[np.ndarray]], sizes: Sequence[float]):
    w = np.asarray(sizes, np.float64)
    w = w / w.sum()
    return [sum(wi * r[j] for wi, r in zip(w, rows))
            for j in range(len(rows[0]))]


def staleness_alphas(fed: dict, stalenesses: Sequence[int]) -> List[float]:
    """Per-row merge weights of the semi-async merge from the
    federation's ``async_alpha``, ``async_staleness`` and ``async_a``."""
    alpha = float(fed["async_alpha"])
    if fed["async_staleness"] == "poly":
        return [alpha * (s + 1.0) ** (-float(fed["async_a"]))
                for s in stalenesses]
    return [alpha] * len(stalenesses)


def staleness_fold(g_in, rows, alphas):
    """``g <- (1 - a_i) g + a_i x_i`` over the rows in merge order, in
    float64."""
    g = [np.asarray(l, np.float64) for l in g_in]
    for a, r in zip(alphas, rows):
        g = [(1.0 - a) * gl + a * rl for gl, rl in zip(g, r)]
    return g


def staleness_fold_in(dtype, g_in, rows, alphas):
    """``staleness_fold`` with every array and weight in ``dtype``."""
    g = [jnp.asarray(l, dtype) for l in g_in]
    for a, r in zip(alphas, rows):
        a = jnp.asarray(a, dtype)
        g = [(1 - a) * gl + a * jnp.asarray(rl, dtype)
             for gl, rl in zip(g, r)]
    return [np.asarray(l.astype(jnp.float32), np.float64) for l in g]


def merge(kind: str, g_in, rows, weights, alphas, dtype=None):
    """The traffic's reference merge (module docstring) of ``rows`` into
    ``g_in``: ``weights`` are the sample counts (``sync_mean``),
    ``alphas`` the per-row staleness weights (``staleness_fold``).
    ``dtype`` computes it in that precision instead of float64."""
    if kind == "sync_mean":
        return (sync_merge(rows, weights) if dtype is None
                else sync_merge_in(dtype, rows, weights))
    if kind == "staleness_fold":
        return (staleness_fold(g_in, rows, alphas) if dtype is None
                else staleness_fold_in(dtype, g_in, rows, alphas))
    raise KeyError(f"no reference merge {kind!r}")


def sync_merge_in(dtype, rows, sizes):
    w = jnp.asarray(np.asarray(sizes, np.float64) / float(np.sum(sizes)), dtype)
    out = []
    for j in range(len(rows[0])):
        acc = jnp.zeros(rows[0][j].shape, dtype)
        for wi, r in zip(w, rows):
            acc = acc + wi * jnp.asarray(r[j], dtype)
        out.append(np.asarray(acc.astype(jnp.float32), np.float64))
    return out


def worst(values: Sequence[float]) -> float:
    return float(max(values)) if values else 0.0


def unique_in_order(ids: Sequence[int]) -> Dict[int, int]:
    """Client id -> its first position (padded slots repeat ids)."""
    out: Dict[int, int] = {}
    for pos, c in enumerate(ids):
        out.setdefault(int(c), pos)
    return out
