"""Operations the Laguna-S-2.1 decoder needs, as functions of shapes: the
counts of ``flops.py`` for the ``laguna_s_2_1`` configuration, and the least
work of the flash kernels' band (``apex_flash_window_*``) for its roofline.

``model`` is the configuration file's ``model`` group: the published counts
and what is held (``layer_types`` / ``mlp_layer_types`` /
``num_attention_heads_per_layer`` the layers kept, ``experts_held`` =
[first, count], ``vocab_size`` the slice).  A multiply-add is 2 FLOPs; what
remat recomputes is not counted, and neither is non-matmul arithmetic
(norms, RoPE, gates).  A window layer's cores are counted over the pairs its
band holds, not over the causal half: what a window layer must do.
"""
from __future__ import annotations

_MACS = 2.0


def attention_params(model: dict, heads: int) -> int:
    """An attention mixer's matrices: ``W_q``, ``W_k``, ``W_v``, ``W_o`` and
    the gate's ``W_g`` (one output a head)."""
    d, hd = model["hidden_size"], model["head_dim"]
    kv = model["num_key_value_heads"] * hd
    return 2 * d * heads * hd + 2 * d * kv + d * heads


def sparse_ffn_params_per_token(model: dict) -> float:
    """What a token meets in a sparse FFN: the router, the shared expert and
    its gate, and the EXPECTED share of its ``num_experts_per_tok``
    assignments that fall on held experts (``k · held / num_experts``: the
    router knows nothing of the cut)."""
    d, m = model["hidden_size"], model["moe_intermediate_size"]
    held_per_token = (model["num_experts_per_tok"] * model["experts_held"][1]
                      / model["num_experts"])
    return (d * model["num_experts"]
            + 3 * d * model["shared_expert_intermediate_size"] + d
            + held_per_token * 3 * d * m)


def matmul_params_per_token(model: dict) -> float:
    """Parameters a token meets in a matrix product: every layer's mixer and
    FFN, and the head.  The embedding is a lookup."""
    d = model["hidden_size"]
    total = model["vocab_size"] * d
    for heads, mlp in zip(model["num_attention_heads_per_layer"],
                          model["mlp_layer_types"]):
        total += attention_params(model, heads) + (
            3 * d * model["intermediate_size"] if mlp == "dense"
            else sparse_ffn_params_per_token(model))
    return total


def band_pairs(seq: int, window: int) -> int:
    """(query, key) pairs of one head's band: query i sees min(i + 1,
    window) keys."""
    inside = min(seq, window)
    return inside * (inside + 1) // 2 + (seq - inside) * window


def attention_flops_per_token(model: dict, seq: int) -> float:
    """Forward FLOPs of the attention cores a token, ``QKᵀ`` and ``PV``: a
    full layer over the causal half (``S · H · 2·hd``), a window layer over
    its band's pairs (``4 · hd · H · pairs / S``)."""
    hd, total = model["head_dim"], 0.0
    for kind, heads in zip(model["layer_types"],
                           model["num_attention_heads_per_layer"]):
        if kind == "sliding_attention":
            total += 2 * _MACS * hd * heads * band_pairs(
                seq, model["sliding_window"]) / seq
        else:
            total += seq * heads * 2 * hd
    return total


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward: three times the forward's ``2·N`` of the weight
    products and its attention cores."""
    return 3.0 * (_MACS * matmul_params_per_token(model)
                  + attention_flops_per_token(model, seq))


def train_flops_per_sample(model: dict, seq: int) -> float:
    return seq * train_flops_per_token(model, seq)


def band_kernel_cost(batch_heads: int, seq: int, head_dim: int, window: int,
                     passes: str, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) one call of the band's flash kernels must do:
    ``flops.attention_kernel_cost`` with the band's pairs in place of the
    causal half — two products a pair forward, five backward (the
    recomputed ``QKᵀ``, ``dP``, ``dV``, ``dQ``, ``dK``); q, k, v read and o
    written once forward, q, k, v, o, do read and dq, dk, dv written once
    backward."""
    products = {"fwd": 2, "bwd": 5}[passes]
    tensors = {"fwd": 4, "bwd": 8}[passes]
    flops = products * _MACS * batch_heads * band_pairs(seq, window) \
        * head_dim
    return flops, float(tensors * batch_heads * seq * head_dim * itemsize)
