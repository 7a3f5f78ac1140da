"""MLP: multi-layer perceptron as one fused call."""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from ..amp import amp as _amp


def _mlp_forward(x, weights, biases, activation="relu"):
    """Chained GEMM + bias + activation.  ``weights[i]`` is (in, out) —
    note the reference stores (out, in) torch-style; we use the natural
    row-major layout for ``x @ w`` on the MXU."""
    h = x
    # activation applies after EVERY layer, matching the reference MLP
    # (tests/L0/run_mlp/test_mlp.py builds Linear+ReLU pairs for all layers)
    for w, b in zip(weights, biases):
        h = jnp.dot(h, w, preferred_element_type=jnp.float32)
        if b is not None:
            h = h + b
        if activation == "relu":
            h = jnp.maximum(h, 0.0)
        elif activation == "sigmoid":
            h = jax.nn.sigmoid(h)
        elif activation != "none":
            raise ValueError(f"unknown activation {activation}")
        h = h.astype(x.dtype)
    return h


# registered as an amp half_function, mirroring mlp.py:24
mlp_function = _amp.half_function(_mlp_forward)


def _mlp_pallas_fwd(x, weights, biases, activation):
    from ..ops.fused_mlp import mlp_pallas
    return mlp_pallas(x, weights, biases, activation)


# the pallas path goes through the SAME amp autocast wrapper so both impls
# see identical precision under amp (O1/O4 patched-function casting)
_mlp_pallas_function = _amp.half_function(_mlp_pallas_fwd)


class MLP:
    """``apex.mlp.MLP`` analog (mlp.py:26-79): sizes = [in, h1, ..., out].

    activation: 'none' | 'relu' | 'sigmoid' (reference supports exactly
    these three, mlp.py:30).
    """

    def __init__(self, mlp_sizes: Sequence[int], bias=True, relu=True,
                 activation=None, use_pallas=False):
        if activation is None:
            activation = "relu" if relu else "none"
        if activation not in ("none", "relu", "sigmoid"):
            raise ValueError(f"activation {activation} not supported")
        self.sizes = list(mlp_sizes)
        self.bias = bias
        self.activation = activation
        # Pallas fused GEMM+epilogue per layer (ops/fused_mlp.py) — the
        # mlp_cuda perf-ceiling analog (SURVEY §2.2); the default is XLA.
        self.use_pallas = bool(use_pallas)

    def init(self, rng):
        """Matches the reference's reset_parameters (mlp.py:64-72):
        weights ~ N(0, sqrt(2/(fan_in+fan_out))) (Xavier-normal), biases
        ~ N(0, sqrt(1/fan_out))."""
        params = {"weights": [], "biases": []}
        keys = jax.random.split(rng, 2 * (len(self.sizes) - 1))
        for i in range(len(self.sizes) - 1):
            fan_in, fan_out = self.sizes[i], self.sizes[i + 1]
            w_std = (2.0 / (fan_in + fan_out)) ** 0.5
            w = jax.random.normal(keys[2 * i], (fan_in, fan_out),
                                  jnp.float32) * w_std
            params["weights"].append(w)
            if self.bias:
                b_std = (1.0 / fan_out) ** 0.5
                b = jax.random.normal(keys[2 * i + 1], (fan_out,),
                                      jnp.float32) * b_std
                params["biases"].append(b)
            else:
                params["biases"].append(None)
        return params

    def apply(self, params, x):
        if self.use_pallas:
            return _mlp_pallas_function(x, params["weights"],
                                        params["biases"], self.activation)
        return mlp_function(x, params["weights"], params["biases"],
                            self.activation)

    __call__ = apply
