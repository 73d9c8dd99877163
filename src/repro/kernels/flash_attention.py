"""Flash attention Pallas TPU kernel: blockwise online softmax.

Grid (BH, nq, nk) with the KV dimension innermost/sequential; running
(acc, m, l) live in VMEM scratch across KV steps.  Block shapes default to
MXU-aligned (128, 128) tiles; q/k/v blocks are staged HBM->VMEM by
BlockSpec.  Causal and sliding-window masks are applied from absolute
positions so the same kernel serves full, causal, and SWA attention.

Heads are folded into the batch dimension (BH = B*H); GQA callers repeat
KV per group in the ops.py wrapper.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bq: int, bk: int, causal: bool, window: int, q_offset: int,
            nk: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                   # (bq, d)
    k = k_ref[0].astype(jnp.float32)                   # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset  # fedlint: disable=FED003 -- int32 index arithmetic, exact regardless of FMA contraction
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)  # fedlint: disable=FED003 -- int32 index arithmetic, exact regardless of FMA contraction
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)  # fedlint: disable=FED003 -- online-softmax rescale; kernel is tolerance-tested vs the reference, not bit-identity-gated
    acc_ref[...] = (acc_ref[...] * corr[:, None]  # fedlint: disable=FED003 -- online-softmax rescale; kernel is tolerance-tested vs the reference, not bit-identity-gated
                    + jax.lax.dot(p.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "q_offset", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q (BH,S,D), k/v (BH,T,D) -> (BH,S,D)."""
    bh, s, d = q.shape
    t = k.shape[1]
    bq = min(block_q, s)
    bk = min(block_k, t)
    if s % bq or t % bk:
        raise ValueError(f"S={s}/T={t} must divide block_q={bq}/block_k={bk}")
    nq, nk = s // bq, t // bk
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, causal=causal, window=window,
        q_offset=q_offset, nk=nk, scale=scale)

    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
