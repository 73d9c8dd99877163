"""The paper's FL workloads: small CNNs (MNIST / Fashion-MNIST) and
ResNet8 (CIFAR-10), pure functional JAX.

CNN (paper §5.1): conv3x3(32) -> pool2 -> conv3x3(64) -> pool2 -> flatten
-> FC(512|128) -> FC(10).  ResNet8: 3 stages of 1 basic block each
(16/32/64 channels), as in arXiv:2204.13399.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.models.layers import dense_init


def _conv_init(key, k, c_in, c_out, dtype):
    w = dense_init(key, (k * k * c_in, c_out), dtype=dtype)
    return w.reshape(k, k, c_in, c_out)


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _conv_im2col(x, w, stride=1):
    """Convolution as patch-extraction + GEMM.

    Under a client-axis vmap (the batched FL engine), per-client kernels
    turn ``_conv`` into a grouped convolution — a slow path on CPU.
    Patch extraction has no weights, so vmap folds it into the batch and
    the weighted contraction becomes a batched GEMM, which is an order
    of magnitude faster on the gradient path.  For stride 1 / odd k the
    patches come from shifted slices of the padded input, whose gradient
    is pure pad-and-add (no scatter, another ~5x on the backward pass).
    """
    k, cin, cout = w.shape[0], w.shape[2], w.shape[3]
    if stride != 1 or k % 2 == 0:
        # general case (strided resnet stages) via the patches op;
        # feature axis ordered (cin, kh, kw)
        p = jax.lax.conv_general_dilated_patches(
            x, (k, k), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        wr = jnp.moveaxis(w, 2, 0).reshape(cin * k * k, cout)
        return p @ wr
    b, h, wd, _ = x.shape
    r = (k - 1) // 2
    xp = jnp.pad(x, ((0, 0), (r, r), (r, r), (0, 0)))
    sl = [xp[:, i:i + h, j:j + wd, :] for i in range(k) for j in range(k)]
    p = jnp.concatenate(sl, axis=-1)     # features ordered (kh, kw, cin)
    return p @ w.reshape(k * k * cin, cout)


def _pool(x):
    b, h, w, c = x.shape
    if h % 2 or w % 2:       # odd spatial dims: generic windowed reduce
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    # 2x2/2 max-pool as reshape+max: identical result, but its gradient
    # avoids XLA's SelectAndScatter (an order of magnitude slower on
    # CPU, and worse under the FL engine's client-axis vmap)
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def init_cnn(cfg: ModelConfig, key, dtype=jnp.float32) -> Dict[str, Any]:
    h, w, c_in = cfg.input_hw
    params: Dict[str, Any] = {}
    if cfg.resnet:
        ks = jax.random.split(key, 2 + 6 * len(cfg.cnn_channels))
        params["stem"] = _conv_init(ks[0], 3, c_in, cfg.cnn_channels[0], dtype)
        c_prev = cfg.cnn_channels[0]
        blocks = []
        ki = 1
        for c in cfg.cnn_channels:
            blk = {
                "conv1": _conv_init(ks[ki], 3, c_prev, c, dtype),
                "conv2": _conv_init(ks[ki + 1], 3, c, c, dtype),
                "scale1": jnp.ones((c,), jnp.float32),
                "scale2": jnp.ones((c,), jnp.float32),
            }
            if c_prev != c:
                blk["proj"] = _conv_init(ks[ki + 2], 1, c_prev, c, dtype)
            blocks.append(blk)
            c_prev = c
            ki += 3
        params["blocks"] = blocks
        params["fc"] = {"w": dense_init(ks[-1], (c_prev, cfg.n_classes),
                                        dtype=dtype),
                        "b": jnp.zeros((cfg.n_classes,), dtype)}
        return params
    # plain CNN
    ks = jax.random.split(key, len(cfg.cnn_channels) + len(cfg.cnn_fc))
    c_prev, ki = c_in, 0
    convs = []
    for c in cfg.cnn_channels:
        convs.append({"w": _conv_init(ks[ki], 3, c_prev, c, dtype),
                      "b": jnp.zeros((c,), dtype)})
        c_prev = c
        ki += 1
    params["convs"] = convs
    flat = (h // 2 ** len(cfg.cnn_channels)) * (w // 2 ** len(cfg.cnn_channels)) * c_prev
    dims = (flat,) + cfg.cnn_fc
    fcs = []
    for a, b in zip(dims[:-1], dims[1:]):
        fcs.append({"w": dense_init(ks[ki], (a, b), dtype=dtype),
                    "b": jnp.zeros((b,), dtype)})
        ki += 1
    params["fcs"] = fcs
    return params


def _norm_act(x, scale):
    # group-norm-ish (batch-independent, FL-friendly: no running stats)
    mu = x.mean(axis=(1, 2), keepdims=True)
    var = x.var(axis=(1, 2), keepdims=True)
    return jax.nn.relu((x - mu) * jax.lax.rsqrt(var + 1e-5) * scale)


def cnn_forward(cfg: ModelConfig, params, images, *, im2col: bool = False):
    """images (B,H,W,C) -> logits (B,n_classes).

    ``im2col=True`` computes every convolution as patches + GEMM — same
    math (to float tolerance), but vmap-friendly; the batched FL engine
    sets it so per-client kernels stay on the fast GEMM path.
    """
    conv = _conv_im2col if im2col else _conv
    x = images
    if cfg.resnet:
        # named scopes: each op's metadata carries its part of the net,
        # so a device trace splits the programs' time by part
        with jax.named_scope("resnet.stem"):
            x = conv(x, params["stem"])
        for i, blk in enumerate(params["blocks"]):
            stride = 1 if i == 0 else 2
            with jax.named_scope(f"resnet.block{i}.conv1"):
                h = conv(x, blk["conv1"], stride)
            with jax.named_scope(f"resnet.block{i}.norm1"):
                h = _norm_act(h, blk["scale1"])
            with jax.named_scope(f"resnet.block{i}.conv2"):
                h = conv(h, blk["conv2"])
            with jax.named_scope(f"resnet.block{i}.shortcut"):
                sc = x if "proj" not in blk else conv(x, blk["proj"], stride)
            with jax.named_scope(f"resnet.block{i}.norm2"):
                x = _norm_act(h + sc, blk["scale2"])
        with jax.named_scope("resnet.head"):
            x = x.mean(axis=(1, 2))
            return x @ params["fc"]["w"] + params["fc"]["b"]
    for cv in params["convs"]:
        x = jax.nn.relu(conv(x, cv["w"]) + cv["b"])
        x = _pool(x)
    x = x.reshape(x.shape[0], -1)
    for i, fc in enumerate(params["fcs"]):
        x = x @ fc["w"] + fc["b"]
        if i < len(params["fcs"]) - 1:
            x = jax.nn.relu(x)
    return x


def cnn_loss(cfg: ModelConfig, params, batch, *, im2col: bool = False):
    logits = cnn_forward(cfg, params, batch["x"],
                         im2col=im2col).astype(jnp.float32)
    labels = batch["y"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def cnn_accuracy(cfg: ModelConfig, params, xs, ys, batch: int = 512):
    correct = 0
    for i in range(0, xs.shape[0], batch):
        logits = cnn_forward(cfg, params, xs[i:i + batch])
        correct += int((jnp.argmax(logits, -1) == ys[i:i + batch]).sum())
    return correct / xs.shape[0]
