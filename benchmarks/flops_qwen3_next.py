"""Operations and bytes the Qwen3-Next decoder needs, as functions of shapes:
the counts of ``flops.py`` for the ``qwen3_next_80b_a3b`` configuration.

``model`` is the configuration file's ``model`` group: the published counts
and what is held (``num_hidden_layers`` the layers kept, ``experts_held`` =
[first, count], ``vocab_size`` the slice).  A multiply-add is 2 FLOPs; what
remat recomputes is not counted, and neither is non-matmul arithmetic (norms,
RoPE, the convolution's four taps, gates) — but for the gated delta rule,
which is counted **as the recurrence** whatever implements it
(:func:`gated_delta_rule_cost`).
"""
from __future__ import annotations

_MACS = 2.0


def layer_types(model: dict) -> list:
    return ["linear_attention" if (i + 1) % model["full_attention_interval"]
            else "full_attention" for i in range(model["num_hidden_layers"])]


def matmul_params_per_token(model: dict) -> float:
    """Parameters a token meets in a matrix product: the mixers' projections,
    the router, the shared expert and its gate, the head — and the EXPECTED
    share of its ``num_experts_per_tok`` assignments that fall on held
    experts (``k · held / num_experts``: the router knows nothing of the
    cut)."""
    d = model["hidden_size"]
    key_w = model["linear_num_key_heads"] * model["linear_key_head_dim"]
    value_w = model["linear_num_value_heads"] * model["linear_value_head_dim"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    mixer = {
        "linear_attention": d * (2 * key_w + 2 * value_w)
        + d * 2 * model["linear_num_value_heads"] + value_w * d,
        "full_attention": d * 2 * q + 2 * d * kv + q * d}
    held_per_token = (model["num_experts_per_tok"] * model["experts_held"][1]
                      / model["num_experts"])
    ffn = (d * model["num_experts"]
           + 3 * d * model["shared_expert_intermediate_size"] + d
           + held_per_token * 3 * d * model["moe_intermediate_size"])
    return model["vocab_size"] * d + sum(       # the head; the embedding is a
        mixer[kind] + ffn for kind in layer_types(model))       # lookup


def gated_delta_rule_cost(tokens: int, heads: int, key_heads: int,
                          key_dim: int, value_dim: int, passes: str,
                          itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) the delta rule of ONE Gated DeltaNet layer must do over
    ``tokens`` steps, whatever implements it: the work of the recurrence
    ``S_t = e^{g_t} S_{t-1} + k_t ⊗ β_t (v_t - (e^{g_t} S_{t-1})ᵀ k_t)``,
    ``o_t = S_tᵀ q_t`` on a state of ``key_dim x value_dim`` a value head.

    ``fwd``: a step and head decays the state (1), reads ``Sᵀk`` (2), adds
    the rank-one update (2) and reads ``Sᵀq`` (2): 7·d_k·d_v FLOPs; bytes
    are ``q``, ``k`` (a key head's, model dtype), ``v`` (model dtype), ``g``
    and ``β`` (float32) read and ``o`` written, once.  ``bwd``: the state's
    cotangent takes ``q ⊗ do`` and ``k ⊗ dr`` (2 + 2) and decays (1), and
    ``dq``, ``dk`` (twice: through the update and through the read), ``dw``
    and ``dg`` each contract it or the state (2 each): 15·d_k·d_v; it reads
    the forward's inputs and ``do`` and writes the five gradients.  States
    kept or recomputed between the passes, chunk-local matrices, the
    triangular system and decays are the implementation's, not the
    algorithm's, and are left out."""
    per_step = heads * key_dim * value_dim
    tensors = (2 * key_heads * key_dim + 2 * heads * value_dim) * itemsize \
        + 2 * 4 * heads                         # q, k | v, o | g, β
    if passes == "fwd":
        return 7.0 * tokens * per_step, float(tokens * tensors)
    if passes == "bwd":
        return 15.0 * tokens * per_step, float(2 * tokens * tensors)
    raise ValueError(f"passes must be 'fwd' or 'bwd', got {passes!r}")


def rule_flops_per_token(model: dict) -> float:
    """Forward + backward FLOPs of the rules of every Gated DeltaNet layer, a
    token."""
    a_layer = sum(gated_delta_rule_cost(
        1, model["linear_num_value_heads"], model["linear_num_key_heads"],
        model["linear_key_head_dim"], model["linear_value_head_dim"],
        passes)[0] for passes in ("fwd", "bwd"))
    return a_layer * layer_types(model).count("linear_attention")


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward: 6·N for the weight products; for every attention
    layer QKᵀ and PV over the causal half, 2·S·(query width) forward a
    token; and the delta rules as the recurrence."""
    causal = (layer_types(model).count("full_attention") * _MACS * seq
              * model["num_attention_heads"] * model["head_dim"])
    return (3.0 * (_MACS * matmul_params_per_token(model) + causal)
            + rule_flops_per_token(model))


def train_flops_per_sample(model: dict, seq: int) -> float:
    return seq * train_flops_per_token(model, seq)
