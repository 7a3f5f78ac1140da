"""Operations and bytes the algorithms need, as functions of shapes.

These are the benchmark's own counts: what the forward and backward passes
REQUIRE (a multiply-add is 2 FLOPs), not what a compiler emitted.  Work that
remat recomputes is not counted, and neither is non-matmul arithmetic, so
``mfu`` built on them is model-FLOPs utilization in the usual sense.
``benchmarks/tests`` cross-checks them against the dot/convolution FLOPs the
program's ``telemetry.attrib.op_table`` reads out of compiled HLO.
"""
from __future__ import annotations

_MACS = 2.0  # FLOPs per multiply-add


def transformer_matmul_params(model: dict) -> int:
    """Parameters that sit in a matrix multiplication of the encoder:
    QKV, attention output, the two MLP matrices, and the tied output head.
    Embedding lookups, positions, norms and biases multiply nothing."""
    d, f = model["d_model"], model["d_ff"]
    per_layer = 3 * d * d + d * d + 2 * d * f
    return model["num_layers"] * per_layer + model["vocab_size"] * d


def transformer_train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward FLOPs per token: 6·N for the weight matmuls
    (2·N forward, twice that backward) plus 12·L·S·d for attention's
    QKᵀ and PV (4·S·d forward per layer, bidirectional, every key)."""
    n = transformer_matmul_params(model)
    attn = 2 * _MACS * seq * model["d_model"] * model["num_layers"]
    return 3.0 * (_MACS * n + attn)


def transformer_train_flops_per_sample(model: dict, seq: int) -> float:
    return seq * transformer_train_flops_per_token(model, seq)


def attention_kernel_cost(batch_heads: int, seq: int, head_dim: int,
                          causal: bool, passes: str,
                          itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) one flash-attention call must do.

    ``passes`` is ``"fwd"`` (QKᵀ and PV) or ``"bwd"`` (the backward as a
    whole, however many kernels it is split into: the recomputed QKᵀ, dP =
    dO·Vᵀ, dV = Pᵀ·dO, dQ = dS·K, dK = dSᵀ·Q — five products against the
    forward's two).  Bytes are each operand and result crossing HBM once:
    forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv (the per-row log-sum-exp is a 1/D-th of that and is
    left out).  A causal mask halves the products."""
    products = {"fwd": 2, "bwd": 5}[passes]
    tensors = {"fwd": 4, "bwd": 8}[passes]
    flops = products * _MACS * batch_heads * seq * seq * head_dim
    if causal:
        flops /= 2.0
    return flops, float(tensors * batch_heads * seq * head_dim * itemsize)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("compute" if t_flops >= t_bytes
                                   else "memory")


def _conv_out(size: int, stride: int) -> int:
    return -(-size // stride)  # SAME padding


def resnet_convs(model: dict, image: int):
    """Every convolution of the network as ``(out_hw, k, cin, cout,
    needs_input_grad)``, in forward order, then the classifier as a 1x1
    convolution over one position.  Strides sit where ``models.resnet``
    puts them (on the 3x3 of a bottleneck — the torchvision layout)."""
    width, bottleneck = model["width"], model["block"] == "bottleneck"
    expansion = 4 if bottleneck else 1
    hw = _conv_out(image, 2)
    convs = [(hw, 7, 3, width, False)]      # nothing upstream wants d(image)
    hw = _conv_out(hw, 2)                    # max pool
    cin = width
    for stage, blocks in enumerate(model["stage_sizes"]):
        cmid = width * 2 ** stage
        cout = cmid * expansion
        for block in range(blocks):
            stride = 2 if (stage > 0 and block == 0) else 1
            out_hw = _conv_out(hw, stride)
            if bottleneck:
                convs += [(hw, 1, cin, cmid, True),
                          (out_hw, 3, cmid, cmid, True),
                          (out_hw, 1, cmid, cout, True)]
            else:
                convs += [(out_hw, 3, cin, cmid, True),
                          (out_hw, 3, cmid, cout, True)]
            if stride != 1 or cin != cout:
                convs.append((out_hw, 1, cin, cout, True))
            hw, cin = out_hw, cout
    convs.append((1, 1, cin, model["num_classes"], True))
    return convs


def resnet_train_flops_per_sample(model: dict, image: int) -> float:
    """Forward + backward FLOPs per image: each convolution once forward,
    once for its weight gradient and once for its input gradient — except
    the stem, whose input is the image."""
    total = 0.0
    for out_hw, k, cin, cout, needs_input_grad in resnet_convs(model, image):
        fwd = _MACS * out_hw * out_hw * k * k * cin * cout
        total += fwd * (3 if needs_input_grad else 2)
    return total


def optimizer_update_bytes(sizes_and_itemsizes, *, master_bytes: int = 4,
                           moment_bytes: int = 4, n_moments: int = 2) -> float:
    """Bytes one optimizer update must move, over ``(size, itemsize)`` of
    each model-precision parameter: read the gradient (it arrives in the
    parameter's dtype), read and write the master weight and each moment,
    write the model-precision copy.  Norms and trust ratios re-read what is
    already counted only if the implementation makes a second pass, which is
    its cost, not the algorithm's."""
    state = 2 * master_bytes + 2 * n_moments * moment_bytes
    return float(sum(size * (2 * itemsize + state)
                     for size, itemsize in sizes_and_itemsizes))


def allreduce_payload_bytes(sizes_and_itemsizes) -> int:
    """Bytes a data-parallel step must reduce: every gradient once, in the
    dtype it is reduced in."""
    return int(sum(size * itemsize for size, itemsize in sizes_and_itemsizes))
