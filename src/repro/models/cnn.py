"""The paper's FL client models (§V-A): small CNN and MLP classifiers.

These are the networks AsyncFLEO trains on-board each satellite (MNIST /
CIFAR-10, 10 classes).  Pure-functional JAX, params as dict pytrees so the
FL aggregation layer treats them identically to the large archs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.paper_models import SmallNetConfig
from repro.models.layers import dense_init


def init_params(key, cfg: SmallNetConfig):
    ks = jax.random.split(key, 6)
    if cfg.kind == "mlp":
        d_in = cfg.image_size * cfg.image_size * cfg.channels
        return {
            "w1": dense_init(ks[0], (d_in, cfg.hidden)),
            "b1": jnp.zeros((cfg.hidden,)),
            "w2": dense_init(ks[1], (cfg.hidden, cfg.hidden)),
            "b2": jnp.zeros((cfg.hidden,)),
            "w3": dense_init(ks[2], (cfg.hidden, cfg.num_classes)),
            "b3": jnp.zeros((cfg.num_classes,)),
        }
    c1, c2 = cfg.conv_channels
    # two 3x3 convs with 2x2 pooling each
    flat = (cfg.image_size // 4) * (cfg.image_size // 4) * c2
    return {
        "conv1": dense_init(ks[0], (3, 3, cfg.channels, c1), in_axis_size=9 * cfg.channels),
        "bc1": jnp.zeros((c1,)),
        "conv2": dense_init(ks[1], (3, 3, c1, c2), in_axis_size=9 * c1),
        "bc2": jnp.zeros((c2,)),
        "w1": dense_init(ks[2], (flat, cfg.hidden)),
        "b1": jnp.zeros((cfg.hidden,)),
        "w2": dense_init(ks[3], (cfg.hidden, cfg.num_classes)),
        "b2": jnp.zeros((cfg.num_classes,)),
    }


def _conv_im2col(x, w, b):
    """3x3 SAME conv as nine shifted slices + one matmul (im2col)."""
    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w.shape
    xp = jnp.pad(x, ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2), (0, 0)))
    patches = jnp.stack([xp[:, i:i + H, j:j + W, :]
                         for i in range(kh) for j in range(kw)], axis=3)
    y = jnp.einsum("bhwkc,kco->bhwo",
                   patches, w.reshape(kh * kw, Cin, Cout))
    return jax.nn.relu(y + b)


def _conv_xla(x, w, b):
    """3x3 SAME conv as XLA's own convolution."""
    y = jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jax.nn.relu(y + b)


def _conv(x, w, b):
    """3x3 SAME conv + bias + ReLU, lowered by platform: the same math at
    XLA's default precision on both.

    XLA:CPU keeps the im2col: its convolutions are slow and compile slowly
    under vmap+grad, while the shifted-slice matmul hits the Eigen GEMM
    path (a 4-participant training call, J=30, b=32, runs 3-4x slower with
    the convolution for MNIST_CNN, 5-6x for CIFAR_CNN).  On the TPU the
    im2col is the cost: the vmapped training step is bound by HBM
    traffic, and the nine-tap patches it materialises, with their
    gradient, take about a third of the paper run's training loop.  There
    XLA's convolution, which ``vmap`` makes one grouped convolution with
    each participant's filters as a feature group, cuts the step's bytes
    (XLA's cost analysis, v5e) by 43% for MNIST_CNN and 57% for CIFAR_CNN.
    ``platform_dependent`` lowers only the branch of the platform compiled
    for, so a compile for a described TPU on a CPU host takes the TPU one.
    """
    with jax.named_scope("conv"):
        return jax.lax.platform_dependent(x, w, b, cpu=_conv_im2col,
                                          default=_conv_xla)


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def apply(params, cfg: SmallNetConfig, images):
    """images: (B, H, W, C) float32 in [0,1]. Returns logits (B, classes)."""
    if cfg.kind == "mlp":
        x = images.reshape(images.shape[0], -1)
        x = jax.nn.relu(x @ params["w1"] + params["b1"])
        x = jax.nn.relu(x @ params["w2"] + params["b2"])
        return x @ params["w3"] + params["b3"]
    x = _conv(images, params["conv1"], params["bc1"])
    x = _pool(x)
    x = _conv(x, params["conv2"], params["bc2"])
    x = _pool(x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["w1"] + params["b1"])
    return x @ params["w2"] + params["b2"]


def loss_fn(params, cfg: SmallNetConfig, images, labels):
    logits = apply(params, cfg, images)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return (logz - gold).mean()


def accuracy(params, cfg: SmallNetConfig, images, labels):
    logits = apply(params, cfg, images)
    return (jnp.argmax(logits, -1) == labels).mean()
