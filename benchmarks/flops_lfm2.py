"""Operations and bytes the LFM2 decoder needs, as functions of shapes: the
counts of ``flops.py`` for the ``lfm2_24b_a2b`` configuration.

``model`` is the configuration file's ``model`` group: the published keys as
cut (``layer_types`` the layers kept, ``num_dense_layers`` the dense ones
among them, ``experts_held`` = [first, count], ``vocab_size`` the slice).
A multiply-add is 2 FLOPs; what remat recomputes is not counted, and neither
is non-matmul arithmetic (norms, RoPE, the convolution's three taps, gates).
``benchmarks/tests`` holds these to the dot FLOPs the program's
``telemetry.attrib.op_table`` reads out of compiled HLO.
"""
from __future__ import annotations

_MACS = 2.0


def matmul_params_per_token(model: dict) -> float:
    """Parameters a token meets in a matrix product: mixers, the dense FFN,
    the router, the head — and, in an expert layer, the EXPECTED share of
    its ``num_experts_per_tok`` assignments that fall on held experts
    (``k · held / num_experts``: the router knows nothing of the cut, so with
    random weights every expert is as likely as any other)."""
    d = model["hidden_size"]
    kv = d // model["num_attention_heads"] * model["num_key_value_heads"]
    mixer = {"conv": d * 3 * d + d * d,
             "full_attention": 2 * d * d + 2 * d * kv}
    held_per_token = (model["num_experts_per_tok"] * model["experts_held"][1]
                      / model["num_experts"])
    total = model["vocab_size"] * d                         # the tied head
    for i, kind in enumerate(model["layer_types"]):
        total += mixer[kind]
        if i < model["num_dense_layers"]:
            total += 3 * d * model["intermediate_size"]
        else:
            total += d * model["num_experts"]               # the router
            total += held_per_token * 3 * d * model["moe_intermediate_size"]
    return total


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward: 6·N for the weight products, and for every
    attention layer QKᵀ and PV over the causal half: 2·S·d forward a
    token."""
    attention = sum(kind == "full_attention"
                    for kind in model["layer_types"])
    causal = attention * _MACS * seq * model["hidden_size"]
    return 3.0 * (_MACS * matmul_params_per_token(model) + causal)


def train_flops_per_sample(model: dict, seq: int) -> float:
    return seq * train_flops_per_token(model, seq)


def grouped_ffn_cost(rows: int, held: int, d_model: int, d_ff: int,
                     passes: str, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) one expert layer's grouped products must do for
    ``rows`` assignments sent to its ``held`` experts, whatever implements
    them.  ``fwd``: three products a row (W1, W3, W2: 6·d·f FLOPs); bytes
    are the rows in and out and the held experts' weights, each crossing
    HBM once.  ``bwd``: each product once for its input and once for its
    weights (12·d·f a row); the rows and their cotangents in, the input's
    cotangent out, the weights in and their gradients out.  The gated
    hidden state (rows x 2f) is the implementation's, not the algorithm's,
    and is left out."""
    weights = held * 3 * d_model * d_ff * itemsize
    if passes == "fwd":
        return (3 * _MACS * rows * d_model * d_ff,
                float(2 * rows * d_model * itemsize + weights))
    if passes == "bwd":
        return (6 * _MACS * rows * d_model * d_ff,
                float(3 * rows * d_model * itemsize + 2 * weights))
    raise ValueError(f"passes must be 'fwd' or 'bwd', got {passes!r}")
