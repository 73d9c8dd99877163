"""Diagonal selective-SSM scan Pallas TPU kernel.

Recurrence (per channel block, diagonal state):
    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t
    y_t = <h_t, C_t>

Grid (B, nd, nt): channel blocks parallel, time chunks sequential; the
running state h (bd, N) stays resident in VMEM scratch across time chunks
(HBM traffic is only the input chunk + output chunk per step — this is
the whole point of the kernel vs. materializing (B,S,D,N) in HBM).
Within a chunk the recurrence steps serially over Q timesteps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, b_ref, c_ref, alog_ref, y_ref, h_ref, *,
            chunk: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a_neg = -jnp.exp(alog_ref[...].astype(jnp.float32))     # (bd, N)

    def step(i, h):
        xt = x_ref[0, i].astype(jnp.float32)                # (bd,)
        dtt = dt_ref[0, i].astype(jnp.float32)              # (bd,)
        bt = b_ref[0, i].astype(jnp.float32)                # (N,)
        ct = c_ref[0, i].astype(jnp.float32)                # (N,)
        da = jnp.exp(dtt[:, None] * a_neg)                  # (bd, N)
        h = da * h + (dtt * xt)[:, None] * bt[None, :]  # fedlint: disable=FED003 -- SSM recurrence; kernel is tolerance-gated vs the numpy oracle, not bit-identity-gated
        y_ref[0, i] = (h @ ct).astype(y_ref.dtype)          # (bd,)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def ssm_scan(x, dt, b_in, c_out, a_log, *, chunk: int = 128,
             block_d: int = 256, interpret: bool = False):
    """x, dt (B,S,D); b_in, c_out (B,S,N); a_log (D,N) -> y (B,S,D)."""
    bsz, s, d = x.shape
    n = b_in.shape[-1]
    bd = min(block_d, d)
    q = min(chunk, s)
    if d % bd or s % q:
        raise ValueError(f"D={d}%{bd} or S={s}%{q} not divisible")
    nd, nt = d // bd, s // q

    kernel = functools.partial(_kernel, chunk=q)
    return pl.pallas_call(
        kernel,
        grid=(bsz, nd, nt),
        in_specs=[
            pl.BlockSpec((1, q, bd), lambda b, j, t: (b, t, j)),
            pl.BlockSpec((1, q, bd), lambda b, j, t: (b, t, j)),
            pl.BlockSpec((1, q, n), lambda b, j, t: (b, t, 0)),
            pl.BlockSpec((1, q, n), lambda b, j, t: (b, t, 0)),
            pl.BlockSpec((bd, n), lambda b, j, t: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, bd), lambda b, j, t: (b, t, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, b_in, c_out, a_log)
