"""Layer: kernels.  The flash forward kernel's share of its roofline: the
least time the chip could take for the calls the trace shows (the larger of
FLOPs over peak and bytes over bandwidth, ``flops.attention_kernel_cost``)
over the time they took."""
from benchmarks import flash


def read(run):
    return flash.roofline_share(run, "fwd")
