"""Operations and bytes the Nemotron-H decoder needs, as functions of shapes:
the counts of ``flops.py`` for the ``nemotron3_super_120b_a12b``
configuration.

``model`` is the configuration file's ``model`` group: the published counts
and what is held (``hybrid_override_pattern`` the layers kept,
``mamba_heads_held`` / ``attention_heads_held`` / ``experts_held`` = [first,
count], ``vocab_size`` the slice).  A multiply-add is 2 FLOPs; what remat
recomputes is not counted, and neither is non-matmul arithmetic (norms, the
convolution's four taps, gates) — but for the state-space scan, which is
counted **as the recurrence** whatever implements it (:func:`ssd_scan_cost`).
``benchmarks/tests`` holds these to the dot FLOPs the program's
``telemetry.attrib.op_table`` reads out of compiled HLO.
"""
from __future__ import annotations

_MACS = 2.0


def held_widths(model: dict) -> dict:
    """Widths of the held share's matrices."""
    heads = model["mamba_heads_held"][1]
    groups = heads // (model["mamba_num_heads"] // model["n_groups"])
    inner = heads * model["mamba_head_dim"]
    q_first, q_heads = model["attention_heads_held"]
    serves = model["num_attention_heads"] // model["num_key_value_heads"]
    kv_heads = (q_first + q_heads - 1) // serves - q_first // serves + 1
    return {"heads": heads, "groups": groups, "inner": inner,
            "in_proj": 2 * inner + 2 * groups * model["ssm_state_size"]
            + heads,
            "q": q_heads * model["head_dim"],
            "kv": kv_heads * model["head_dim"]}


def matmul_params_per_token(model: dict) -> float:
    """Parameters a token meets in a matrix product: the mixers' projections,
    the router, the latent projections, the shared expert, the head — and,
    in an ``E`` layer, the EXPECTED share of its ``num_experts_per_tok``
    assignments that fall on held experts (``k · held / n_routed_experts``:
    the router knows nothing of the cut)."""
    d, ell = model["hidden_size"], model["moe_latent_size"]
    w = held_widths(model)
    held_per_token = (model["num_experts_per_tok"] * model["experts_held"][1]
                      / model["n_routed_experts"])
    per_layer = {
        "M": d * w["in_proj"] + w["inner"] * d,
        "*": 2 * d * w["q"] + 2 * d * w["kv"],
        "E": d * model["n_routed_experts"] + 2 * d * ell
        + 2 * d * model["moe_shared_expert_intermediate_size"]
        + held_per_token * 2 * ell * model["moe_intermediate_size"]}
    return model["vocab_size"] * d + sum(      # the head; the embedding is a
        per_layer[kind]                        # lookup
        for kind in model["hybrid_override_pattern"])


def ssd_scan_cost(tokens: int, heads: int, head_dim: int, groups: int,
                  state: int, passes: str, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) the scan of ONE Mamba-2 layer must do over ``tokens``
    steps, whatever implements it: the work of the recurrence ``h_t =
    exp(Δ_t A) h_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = C_t·h_t`` on a state of
    ``head_dim x state`` a head.

    ``fwd``: a step and head decays the state, adds the outer product and
    contracts with C: 5·P·N FLOPs; bytes are ``x``, ``B``, ``C`` (model
    dtype) and ``Δ`` (float32) read and ``y`` written, once.  ``bwd``: the
    state's cotangent decays and takes ``C_t ⊗ dy_t`` (3·P·N), and ``dx``,
    ``dB``, ``dC`` and ``d(ΔA)`` each contract it or the state (2·P·N each):
    11·P·N; it reads the forward's inputs and ``dy`` and writes the four
    gradients.  States kept or recomputed between the passes, chunk-local
    matrices and decays are the implementation's, not the algorithm's, and
    are left out."""
    per_step = heads * head_dim * state
    tensors = (2 * heads * head_dim + 2 * groups * state) * itemsize \
        + 4 * heads                          # x, y | B, C | Δ
    if passes == "fwd":
        return 5.0 * tokens * per_step, float(tokens * tensors)
    if passes == "bwd":
        return 11.0 * tokens * per_step, float(2 * tokens * tensors)
    raise ValueError(f"passes must be 'fwd' or 'bwd', got {passes!r}")


def scan_flops_per_token(model: dict) -> float:
    """Forward + backward FLOPs of the scans of every ``M`` layer, a token."""
    w = held_widths(model)
    a_layer = sum(ssd_scan_cost(
        1, w["heads"], model["mamba_head_dim"], w["groups"],
        model["ssm_state_size"], passes)[0] for passes in ("fwd", "bwd"))
    return a_layer * model["hybrid_override_pattern"].count("M")


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward: 6·N for the weight products; for every attention
    layer QKᵀ and PV over the causal half, 2·S·(held query width) forward a
    token; and the scans as the recurrence."""
    causal = (model["hybrid_override_pattern"].count("*") * _MACS * seq
              * held_widths(model)["q"])
    return (3.0 * (_MACS * matmul_params_per_token(model) + causal)
            + scan_flops_per_token(model))


def train_flops_per_sample(model: dict, seq: int) -> float:
    return seq * train_flops_per_token(model, seq)
