"""Operations the GLM-4.7-Flash decoder needs, as functions of shapes: the
counts of ``flops.py`` for the ``glm47_flash_30b_a3b`` configuration.

``model`` is the configuration file's ``model`` group: the published counts
and what is held (``num_hidden_layers`` the layers kept, the leading
``first_k_dense_replace`` of them dense, ``experts_held`` = [first, count],
``vocab_size`` the slice, ``num_nextn_predict_layers`` the MTP modules).  A
multiply-add is 2 FLOPs; what remat recomputes is not counted, and neither
is non-matmul arithmetic (norms, RoPE, the key's assembly, gates).
"""
from __future__ import annotations

_MACS = 2.0


def mla_params(model: dict) -> int:
    """A latent-attention mixer's matrices: ``W_qa``, ``W_qb``, ``W_kva``,
    ``W_kvb``, ``W_o``."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    ql, kl = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    vd = model["v_head_dim"]
    return (d * ql + ql * heads * (nope + rope) + d * (kl + rope)
            + kl * heads * (nope + vd) + heads * vd * d)


def sparse_ffn_params_per_token(model: dict) -> float:
    """What a token meets in a sparse FFN: the router, the shared expert and
    the EXPECTED share of its ``num_experts_per_tok`` assignments that fall
    on held experts (``k · held / num_experts``: the router knows nothing of
    the cut)."""
    d, m = model["hidden_size"], model["moe_intermediate_size"]
    held_per_token = (model["num_experts_per_tok"] * model["experts_held"][1]
                      / model["num_experts"])
    return (d * model["num_experts"] + 3 * d * m * model["n_shared_experts"]
            + held_per_token * 3 * d * m)


def matmul_params_per_token(model: dict) -> float:
    """Parameters a token meets in a matrix product: every layer's mixer and
    FFN, the head, and each MTP module's join (``W_eh``), block and second
    pass through the head.  The embedding is a lookup."""
    d, layers = model["hidden_size"], model["num_hidden_layers"]
    dense = model["first_k_dense_replace"]
    head = model["vocab_size"] * d
    total = (layers * mla_params(model)
             + dense * 3 * d * model["intermediate_size"]
             + (layers - dense) * sparse_ffn_params_per_token(model) + head)
    return total + model["num_nextn_predict_layers"] * (
        2 * d * d + mla_params(model) + sparse_ffn_params_per_token(model)
        + head)


def attention_flops_per_token(model: dict, seq: int) -> float:
    """Forward FLOPs of the causal cores a token: in every latent-attention
    layer, the MTP module's among them, ``QKᵀ`` and ``PV`` over the causal
    half — ``S · H · (qk width + v width)``."""
    mla_layers = (model["num_hidden_layers"]
                  + model["num_nextn_predict_layers"])
    width = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
             + model["v_head_dim"])
    return mla_layers * seq * model["num_attention_heads"] * width


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward: three times the forward's ``2·N`` of the weight
    products and its causal cores."""
    return 3.0 * (_MACS * matmul_params_per_token(model)
                  + attention_flops_per_token(model, seq))


def train_flops_per_sample(model: dict, seq: int) -> float:
    return seq * train_flops_per_token(model, seq)
