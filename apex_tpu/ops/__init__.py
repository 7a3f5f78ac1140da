"""Pallas TPU kernels for structured ops (the ``csrc/`` analog).

Unlike the elementwise multi-tensor engine (which measured faster as XLA
fusions over flat buffers — PERF_NOTES.md §2), the ops here have reduction /
blocking structure that benefits from explicit kernels: layer norm (the
``fused_layer_norm_cuda`` analog), with flash attention and fused
softmax-xentropy living in ``apex_tpu.contrib``; and
``ops.gated_delta_rule``, the chunked gated delta rule of the Qwen3-Next
mixers as a forward / sweep / reverse kernel set with its state in VMEM.
"""
from .layer_norm import layer_norm_pallas, pallas_available
from .fused_mlp import dense_act, fused_dense_act, mlp_pallas

__all__ = ["layer_norm_pallas", "pallas_available", "dense_act",
           "fused_dense_act", "mlp_pallas"]
