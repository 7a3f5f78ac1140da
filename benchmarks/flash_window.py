"""What the band readers share: the roofline share of the flash kernels of a
sliding window (``apex_flash_window_fwd``, ``apex_flash_window_bwd_dq``,
``apex_flash_window_bwd_dkv``; ``flash.kernel_of`` reads them as
``window_fwd`` / ``window_bwd_*``, which the causal readers do not count).
The band's shape is the job's ``facts["window"]``."""
from __future__ import annotations

from benchmarks import flash, flops, flops_laguna


def roofline_share(run, passes: str):
    """Least time for the band's ``passes`` ("fwd" / "bwd") the trace shows
    over the time their kernels took, in %.  A backward is one pass in two
    kernels, counted by its ``dq`` half; remat runs the forward twice a
    layer, and the trace counts both."""
    shape = run.job.facts.get("window")
    if not run.trace or not shape:
        return None
    name = {"fwd": "window_fwd", "bwd": "window_bwd_dq"}[passes]
    dev = run.trace.devices[0]
    calls = dev.count(lambda ev: flash.kernel_of(ev) == name)
    spent_ns = dev.self_ns(
        lambda ev: (flash.kernel_of(ev) or "").startswith("window_" + passes))
    if not calls or not spent_ns:
        return None
    need_flops, need_bytes = flops_laguna.band_kernel_cost(
        shape["batch_heads"], shape["seq"], shape["head_dim"],
        shape["window"], passes, shape["itemsize"])
    least_s, bound = flops.roofline_seconds(need_flops, need_bytes, run.peaks)
    took = spent_ns / calls
    print(f"[bench] band {passes} kernel: {calls} calls, {took / 1e3:.1f} us "
          f"each, {took / shape['batch_heads'] / 1e3:.2f} us a head, least "
          f"{least_s * 1e6:.1f} us ({bound}-bound)", flush=True)
    causal = ("fwd",) if passes == "fwd" else ("bwd_fused", "bwd_dq")
    c_calls = dev.count(lambda ev: flash.kernel_of(ev) in causal)
    c_ns = dev.self_ns(lambda ev: (flash.kernel_of(ev) or "").startswith(
        passes))
    full = run.job.facts.get("attention")
    if c_calls and full:
        print(f"[bench] causal {passes} kernel beside it: {c_calls} calls, "
              f"{c_ns / c_calls / full['batch_heads'] / 1e3:.2f} us a head",
              flush=True)
    return 100.0 * calls * least_s * 1e9 / spent_ns
