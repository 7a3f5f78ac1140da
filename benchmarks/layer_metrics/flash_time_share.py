"""Layer: kernels (``contrib.multihead_attn.flash``).  Device time of the
events named ``apex_flash_*`` — the ``name=`` every flash ``pallas_call``
carries — over device busy time in the traced steps.  0 in a cell whose model
has no attention."""
from benchmarks import flash


def read(run):
    if not run.trace:
        return None
    return run.trace.share_of_busy(flash.is_flash)
