"""Pallas kernel allclose sweeps vs pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (fedagg_fold_op, fedagg_op, fedagg_partial_op,
                           gqa_flash_attention, ssm_scan_op)
from repro.kernels.fedagg import PANEL_BYTES, default_block_p
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import (fedagg_fold_ref, fedagg_partial_ref,
                               fedagg_ref, flash_attention_ref,
                               ssm_scan_ref)

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,t,d,bq,bk", [
    (128, 128, 64, 64, 64),
    (256, 256, 32, 128, 128),
    (64, 256, 64, 64, 64),      # cross-attention shape
    (256, 128, 16, 64, 128),    # small head_dim, uneven blocks
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shapes(s, t, d, bq, bk, causal):
    if causal and s > t:
        pytest.skip("causal with s>t undefined here")
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (3, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (3, t, d), jnp.float32)
    v = jax.random.normal(ks[2], (3, t, d), jnp.float32)
    off = t - s if causal else 0
    out = flash_attention(q, k, v, causal=causal, q_offset=off,
                          block_q=bq, block_k=bk, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, q_offset=off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_tol(jnp.float32))


@pytest.mark.parametrize("window", [32, 100, 256])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 256, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 256, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 256, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_tol(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 64)).astype(dtype)
    k = jax.random.normal(ks[1], (2, 128, 64)).astype(dtype)
    v = jax.random.normal(ks[2], (2, 128, 64)).astype(dtype)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_gqa_wrapper_matches_model_attention():
    from repro.models.attention import naive_attention, repeat_kv
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 8, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 128, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 128, 2, 32), jnp.float32)
    out = gqa_flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
    ref = naive_attention(q, repeat_kv(k, 8), repeat_kv(v, 8), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,d,n,chunk,bd", [
    (2, 64, 32, 8, 16, 16),
    (1, 128, 64, 16, 32, 64),
    (3, 32, 16, 4, 32, 8),
])
def test_ssm_scan_shapes(b, s, d, n, chunk, bd):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (b, s, d), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d)))
    b_in = jax.random.normal(ks[2], (b, s, n), jnp.float32)
    c_out = jax.random.normal(ks[3], (b, s, n), jnp.float32)
    a_log = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[None].repeat(d, 0)
    y = ssm_scan_op(x, dt, b_in, c_out, a_log, chunk=chunk, block_d=bd,
                    interpret=True)
    yr = ssm_scan_ref(x, dt, b_in, c_out, a_log)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)


def test_ssm_scan_bf16():
    ks = jax.random.split(KEY, 4)
    b, s, d, n = 2, 64, 32, 8
    x = jax.random.normal(ks[0], (b, s, d)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d))).astype(jnp.bfloat16)
    b_in = jax.random.normal(ks[2], (b, s, n)).astype(jnp.bfloat16)
    c_out = jax.random.normal(ks[3], (b, s, n)).astype(jnp.bfloat16)
    a_log = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[None].repeat(d, 0)
    y = ssm_scan_op(x, dt, b_in, c_out, a_log, chunk=16, block_d=16,
                    interpret=True)
    yr = ssm_scan_ref(x, dt, b_in, c_out, a_log)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# fedagg
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,p,bp", [(3, 300, 128), (8, 4096, 1024),
                                    (50, 999, 256), (1, 17, 128)])
def test_fedagg_shapes(n, p, bp):
    ks = jax.random.split(KEY, 2)
    u = jax.random.normal(ks[0], (n, p), jnp.float32)
    w = jnp.abs(jax.random.normal(ks[1], (n,))) + 0.1
    out = fedagg_op(u, w, block_p=bp, interpret=True)
    ref = fedagg_ref(u, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedagg_dtypes(dtype):
    u = (jnp.arange(12.0).reshape(3, 4) / 10).astype(dtype)
    w = jnp.asarray([1.0, 1.0, 2.0])
    out = fedagg_op(u, w, block_p=128, interpret=True)
    ref = fedagg_ref(u, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# fedagg_fold (implicit global row 0) and fedagg_partial (per-shard sum)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,p,bp", [(3, 300, 128), (8, 999, 256),
                                    (1, 17, 128)])
def test_fedagg_fold_shapes(k, p, bp):
    ks = jax.random.split(KEY, 3)
    u = jax.random.normal(ks[0], (k, p), jnp.float32)
    g = jax.random.normal(ks[1], (p,), jnp.float32)
    coef = jnp.abs(jax.random.normal(ks[2], (k + 1,))) + 0.05
    out = fedagg_fold_op(u, g, coef, block_p=bp, interpret=True)
    ref = fedagg_fold_ref(u, g, coef)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_fedagg_fold_zero_coef_rows_masked_even_nonfinite():
    u = jnp.asarray([[1.0, 2.0], [np.nan, np.inf]], jnp.float32)
    g = jnp.asarray([4.0, 8.0], jnp.float32)
    coef = jnp.asarray([0.5, 0.5, 0.0], jnp.float32)
    out = fedagg_fold_op(u, g, coef, interpret=True)
    np.testing.assert_allclose(np.asarray(out), [2.5, 5.0], rtol=1e-6)


def test_fedagg_fold_padded_zero_rows_are_bitwise_noops():
    """The store's fused window pads cohorts with zero-coefficient
    rows; the kernel's masked multiply+sum must keep padded and
    unpadded windows BITWISE equal (the store-vs-dict history gate)."""
    ks = jax.random.split(KEY, 3)
    u = jax.random.normal(ks[0], (5, 403), jnp.float32)
    g = jax.random.normal(ks[1], (403,), jnp.float32)
    coef = jnp.abs(jax.random.normal(ks[2], (6,))) + 0.05
    out = fedagg_fold_op(u, g, coef, block_p=128, interpret=True)
    u_pad = jnp.concatenate([u, jnp.full((3, 403), np.nan, jnp.float32)])
    coef_pad = jnp.concatenate([coef, jnp.zeros(3, jnp.float32)])
    out_pad = fedagg_fold_op(u_pad, g, coef_pad, block_p=128,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_pad))


def test_fedagg_fold_all_zero_coef_gives_zeros():
    u = jnp.ones((3, 5), jnp.float32)
    g = jnp.ones((5,), jnp.float32)
    out = fedagg_fold_op(u, g, jnp.zeros(4), interpret=True)
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=0)


@pytest.mark.parametrize("n,p,bp", [(4, 300, 128), (7, 513, 128)])
def test_fedagg_partial_shapes(n, p, bp):
    ks = jax.random.split(KEY, 2)
    u = jax.random.normal(ks[0], (n, p), jnp.float32)
    c = jnp.abs(jax.random.normal(ks[1], (n,)))
    out = fedagg_partial_op(u, c, block_p=bp, interpret=True)
    ref = fedagg_partial_ref(u, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_fedagg_partial_is_unnormalized_and_masked():
    u = jnp.asarray([[2.0, 4.0], [np.nan, np.nan], [1.0, 1.0]],
                    jnp.float32)
    c = jnp.asarray([0.5, 0.0, 2.0], jnp.float32)
    out = fedagg_partial_op(u, c, interpret=True)
    np.testing.assert_allclose(np.asarray(out), [3.0, 4.0], rtol=1e-6)


def test_fedagg_block_p_must_be_lane_aligned():
    u = jnp.ones((3, 300), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 128"):
        fedagg_partial_op(u, jnp.ones(3), block_p=64, interpret=True)


@pytest.mark.parametrize("k", [1, 16, 64, 256, 4096])
def test_fedagg_default_block_p_fits_vmem_panel(k):
    """The default panel is lane-aligned and its (K, block_p) f32 input
    stays within the budget that leaves room for double buffering in
    v5e's 16 MiB scoped VMEM."""
    bp = default_block_p(k)
    assert bp % 128 == 0
    assert max(k, 8) * bp * 4 <= PANEL_BYTES or bp == 128
