"""Plain reference of the paper's MNIST / Fashion-MNIST CNN (FedDCT §5.1).

    conv3x3(c_1) + b -> relu -> maxpool 2x2 -> ... -> conv3x3(c_k) + b
    -> relu -> maxpool 2x2 -> flatten (H, W, C order) -> FC + relu ...
    -> FC(n_classes)

``init``, ``forward``, ``loss`` and ``flops_per_sample`` are what the
benchmark asks of every model module.  Straight ``jax.numpy`` / ``lax``
with an explicit dtype and matmul precision, no kernels and no batching
tricks.  The parameter pytree has
the layout the served trainer takes (``{"convs": [{"w", "b"}, ...],
"fcs": [{"w", "b"}, ...]}``, conv kernels HWIO), so the benchmark can
hand the weights it makes to both sides.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _fan_in_normal(key, shape, fan_in):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            / jnp.sqrt(jnp.float32(fan_in)))


def init(sizes: dict, key):
    """Weights from ``key``: truncated normal / sqrt(fan_in), zero bias."""
    h, w, c_in = sizes["input_hw"]
    k = sizes["kernel"]
    chans, fcs = sizes["channels"], sizes["fc"]
    keys = jax.random.split(key, len(chans) + len(fcs))
    convs, c_prev = [], c_in
    for i, c in enumerate(chans):
        convs.append({"w": _fan_in_normal(keys[i], (k, k, c_prev, c),
                                          k * k * c_prev),
                      "b": jnp.zeros((c,), jnp.float32)})
        c_prev = c
    d_prev = (h >> len(chans)) * (w >> len(chans)) * c_prev
    dense = []
    for j, d in enumerate(fcs):
        dense.append({"w": _fan_in_normal(keys[len(chans) + j], (d_prev, d),
                                          d_prev),
                      "b": jnp.zeros((d,), jnp.float32)})
        d_prev = d
    return {"convs": convs, "fcs": dense}


def forward(sizes: dict, params, x, precision):
    """images (B, H, W, C) -> logits (B, n_classes), in ``x.dtype``."""
    for cv in params["convs"]:
        x = lax.conv_general_dilated(
            x, cv["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        x = jnp.maximum(x + cv["b"], 0)
        x = lax.reduce_window(x, -jnp.inf, lax.max,
                              (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    for i, fc in enumerate(params["fcs"]):
        x = jnp.dot(x, fc["w"], precision=precision) + fc["b"]
        if i < len(params["fcs"]) - 1:
            x = jnp.maximum(x, 0)
    return x


def loss(sizes: dict, params, x, y, precision):
    """Mean cross-entropy of ``forward``'s logits against labels y (B,)."""
    logits = forward(sizes, params, x, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def flops_per_sample(sizes: dict) -> int:
    """Forward + backward FLOPs of one sample, from the layer shapes:
    2 per multiply-add of every conv and dense layer, times 3 (the
    backward pass computes two products for each one of the forward).
    Bias, relu, pooling and the loss are not counted."""
    h, w, c_prev = sizes["input_hw"]
    k = sizes["kernel"]
    fwd = 0
    for c in sizes["channels"]:
        fwd += 2 * h * w * k * k * c_prev * c
        h, w, c_prev = h // 2, w // 2, c
    d_prev = h * w * c_prev
    for d in sizes["fc"]:
        fwd += 2 * d_prev * d
        d_prev = d
    return 3 * fwd
