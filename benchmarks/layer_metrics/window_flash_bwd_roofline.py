"""Layer: kernels.  The share of their roofline that the band's flash
backward kernels (``apex_flash_window_bwd_dq`` and ``_dkv``, a sliding
window's) reach: the least time the chip could take for the backward passes
the trace shows (the larger of FLOPs over peak and bytes over bandwidth,
over the band's pairs: ``flops_laguna.band_kernel_cost``) over the time both
kernels took.  Nothing where the program has no band kernel."""
from benchmarks import flash_window


def read(run):
    return flash_window.roofline_share(run, "bwd")
