"""Layer: kernels.  The share of its roofline that the band's flash forward
kernel (``apex_flash_window_fwd``, a sliding window's) reaches: the least
time the chip could take for the calls the trace shows (the larger of FLOPs
over peak and bytes over bandwidth, over the band's pairs:
``flops_laguna.band_kernel_cost``) over the time they took.  Nothing where
the program has no band kernel."""
from benchmarks import flash_window


def read(run):
    return flash_window.roofline_share(run, "fwd")
