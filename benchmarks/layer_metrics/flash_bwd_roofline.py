"""Layer: kernels.  The flash backward's share of its roofline, whichever
kernels it is split into (``apex_flash_bwd_fused``, or ``_dq`` + ``_dkv``):
the least time for the backward passes the trace shows over the time their
kernels took."""
from benchmarks import flash


def read(run):
    return flash.roofline_share(run, "bwd")
