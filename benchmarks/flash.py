"""What the flash-attention readers share: which trace events are the
kernel's, and its roofline share.

The program names every flash ``pallas_call`` (``apex_flash_fwd``,
``apex_flash_bwd_fused``, ``apex_flash_bwd_dq``, ``apex_flash_bwd_dkv``); the
trace carries that name.
"""
from __future__ import annotations

from benchmarks import flops, reduce

PREFIX = "apex_flash_"


def kernel_of(event):
    """``fwd`` / ``bwd_fused`` / ``bwd_dq`` / ``bwd_dkv`` for a flash kernel's
    event (``%apex_flash_bwd_fused.10 = ... custom-call(...)``), else None."""
    name = reduce.base_name(event.name)
    return name[len(PREFIX):] if name.startswith(PREFIX) else None


def is_flash(event) -> bool:
    return kernel_of(event) is not None


def roofline_share(run, passes: str):
    """Least time for the ``passes`` ("fwd" / "bwd") the trace shows over the
    time their kernels took, in %.  The number of passes is counted from the
    trace (remat runs the forward twice a layer; a split backward is one pass
    in two kernels, counted by its ``dq`` half)."""
    shape = run.job.facts.get("attention")
    if not run.trace or not shape:
        return None
    counted = {"fwd": ("fwd",), "bwd": ("bwd_fused", "bwd_dq")}[passes]
    dev = run.trace.devices[0]
    calls = dev.count(lambda ev: kernel_of(ev) in counted)
    spent_ns = dev.self_ns(
        lambda ev: (kernel_of(ev) or "").startswith(passes))
    if not calls or not spent_ns:
        return None
    need_flops, need_bytes = flops.attention_kernel_cost(
        shape["batch_heads"], shape["seq"], shape["head_dim"],
        shape["causal"], passes, shape["itemsize"])
    least_s, bound = flops.roofline_seconds(need_flops, need_bytes, run.peaks)
    print(f"[bench] flash {passes} kernel: {calls} calls, "
          f"{spent_ns / calls / 1e3:.1f} us each, least "
          f"{least_s * 1e6:.1f} us ({bound}-bound)", flush=True)
    return 100.0 * calls * least_s * 1e9 / spent_ns
