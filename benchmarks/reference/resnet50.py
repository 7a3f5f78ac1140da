"""Plain float32 reference of the network the ``resnet50`` configuration
trains: forward pass in training mode and the classification loss.

ResNet (He et al., arXiv:1512.03385): a 7x7 stride-2 stem, a 3x3 stride-2
max pool, four stages of residual blocks, global average pool, a linear
classifier.  Batch norm uses the batch's own statistics (biased variance,
eps 1e-5).  No mixed precision and no loss scaling; convolutions at
``precision="highest"`` through the caller's ``jax.default_matmul_precision``.
``lax.conv_general_dilated`` is the convolution itself, not a kernel of the
program.  Gradients are ``jax.grad`` of :func:`loss`.

Departure from the paper, the program's choice: in a bottleneck block that
halves the resolution the stride sits on the 3x3 convolution (the torchvision
"v1.5" layout), not on the first 1x1.

Parameters arrive in the program's own tree (they are data): ``conv_init``,
``bn_init/{scale,bn_bias}``, ``stage<s>_block<b>/{conv1..3, bn1..3/{scale,
bn_bias}, conv_proj, bn_proj}``, ``fc_w``, ``fc_b``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-5


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean((x - mean) ** 2, axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + _EPS) * p["scale"] + p["bn_bias"]


def logits(params, images, model):
    """images (N, H, W, 3) float32 -> logits (N, classes) float32."""
    bottleneck = model["block"] == "bottleneck"
    x = _conv(images, params["conv_init"], 2)
    x = jax.nn.relu(_batch_norm(x, params["bn_init"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for stage, blocks in enumerate(model["stage_sizes"]):
        for block in range(blocks):
            p = params[f"stage{stage}_block{block}"]
            stride = 2 if (stage > 0 and block == 0) else 1
            if bottleneck:
                y = jax.nn.relu(_batch_norm(_conv(x, p["conv1"]), p["bn1"]))
                y = jax.nn.relu(_batch_norm(_conv(y, p["conv2"], stride),
                                            p["bn2"]))
                y = _batch_norm(_conv(y, p["conv3"]), p["bn3"])
            else:
                y = jax.nn.relu(_batch_norm(_conv(x, p["conv1"], stride),
                                            p["bn1"]))
                y = _batch_norm(_conv(y, p["conv2"]), p["bn2"])
            if "conv_proj" in p:
                x = _batch_norm(_conv(x, p["conv_proj"], stride),
                                p["bn_proj"])
            x = jax.nn.relu(y + x)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["fc_w"] + params["fc_b"]


def loss(params, batch, model):
    """Mean cross entropy of ``labels`` under softmax(logits)."""
    images, labels = batch
    lg = logits(params, images, model)
    lg = lg - jnp.max(lg, axis=-1, keepdims=True)
    log_probs = lg - jnp.log(jnp.sum(jnp.exp(lg), axis=-1, keepdims=True))
    return -jnp.mean(jnp.take_along_axis(log_probs, labels[:, None],
                                         axis=1))
