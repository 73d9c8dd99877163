"""Plain reference of the paper's CIFAR-10 ResNet8 (FedDCT §5.1; He et
al., arXiv:1512.03385 §4.2, the CIFAR family of 6n+2 weighted layers at
n = 1, widths 16/32/64).

    conv3x3(c_0) -> for each stage i (width c_i, stride s_i):
        h = relu(norm(conv3x3(x, stride s_i)) * scale1)
        h = conv3x3(h)
        x = relu(norm(h + shortcut(x)) * scale2)
    -> global average pool -> FC(n_classes)

As the program has it, and unlike He et al.:

* the norm is per sample and per channel over H and W (biased
  variance, eps 1e-5) with a learned scale and no shift: not
  BatchNorm, so there are no running statistics, which suits FL;
* the shortcut is a 1x1 projection at the block's stride wherever the
  width changes (option B), not a zero-padded identity (option A);
* the convolutions have no bias, and the stem has no norm of its own.

``init``, ``forward``, ``loss`` and ``flops_per_sample`` are what the
benchmark asks of every model module.  Straight ``jax.numpy`` / ``lax``
with an explicit dtype and matmul precision, plain
``lax.conv_general_dilated`` (``SAME``, the stage's stride) and no
patch extraction.  The parameter pytree has the layout the served
trainer takes (``{"stem", "blocks": [{"conv1", "conv2", "scale1",
"scale2", "proj"?}, ...], "fc": {"w", "b"}}``, conv kernels HWIO), so
the benchmark can hand the weights it makes to both sides.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5


def _fan_in_normal(key, shape, fan_in):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            / jnp.sqrt(jnp.float32(fan_in)))


def init(sizes: dict, key):
    """Weights from ``key``: truncated normal / sqrt(fan_in), unit
    scales, zero FC bias."""
    c_in = sizes["input_hw"][2]
    k = sizes["kernel"]
    chans = sizes["channels"]
    keys = jax.random.split(key, 2 + 3 * len(chans))
    stem = _fan_in_normal(keys[0], (k, k, c_in, chans[0]), k * k * c_in)
    blocks, c_prev = [], chans[0]
    for i, c in enumerate(chans):
        kk = keys[1 + 3 * i:4 + 3 * i]
        blk = {"conv1": _fan_in_normal(kk[0], (k, k, c_prev, c),
                                       k * k * c_prev),
               "conv2": _fan_in_normal(kk[1], (k, k, c, c), k * k * c),
               "scale1": jnp.ones((c,), jnp.float32),
               "scale2": jnp.ones((c,), jnp.float32)}
        if c != c_prev:
            blk["proj"] = _fan_in_normal(kk[2], (1, 1, c_prev, c), c_prev)
        blocks.append(blk)
        c_prev = c
    fc = {"w": _fan_in_normal(keys[-1], (c_prev, sizes["n_classes"]), c_prev),
          "b": jnp.zeros((sizes["n_classes"],), jnp.float32)}
    return {"stem": stem, "blocks": blocks, "fc": fc}


def _conv(x, w, stride, precision):
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _norm_relu(x, scale):
    mu = jnp.mean(x, axis=(1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=(1, 2), keepdims=True)
    return jnp.maximum((x - mu) * lax.rsqrt(var + EPS) * scale, 0)


def forward(sizes: dict, params, x, precision):
    """images (B, H, W, C) -> logits (B, n_classes), in ``x.dtype``."""
    x = _conv(x, params["stem"], 1, precision)
    for blk, stride in zip(params["blocks"], sizes["strides"]):
        h = _norm_relu(_conv(x, blk["conv1"], stride, precision),
                       blk["scale1"])
        h = _conv(h, blk["conv2"], 1, precision)
        sc = _conv(x, blk["proj"], stride, precision) if "proj" in blk else x
        x = _norm_relu(h + sc, blk["scale2"])
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, params["fc"]["w"], precision=precision) + params["fc"]["b"]


def loss(sizes: dict, params, x, y, precision):
    """Mean cross-entropy of ``forward``'s logits against labels y (B,)."""
    logits = forward(sizes, params, x, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def flops_per_sample(sizes: dict) -> int:
    """Forward + backward FLOPs of one sample, from the layer shapes:
    2 per multiply-add of every conv (stem, both convs of each block,
    the projections) and of the FC layer, times 3 (the backward pass
    computes two products for each one of the forward).  Norms, relus,
    residual adds, pooling and the loss are not counted."""
    h, w, c_prev = sizes["input_hw"]
    k = sizes["kernel"]
    chans = sizes["channels"]
    fwd = 2 * h * w * k * k * c_prev * chans[0]
    c_prev = chans[0]
    for c, s in zip(chans, sizes["strides"]):
        h, w = -(-h // s), -(-w // s)
        fwd += 2 * h * w * k * k * (c_prev * c + c * c)
        if c != c_prev:
            fwd += 2 * h * w * c_prev * c
        c_prev = c
    fwd += 2 * c_prev * sizes["n_classes"]
    return 3 * fwd
