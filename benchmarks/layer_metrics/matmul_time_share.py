"""Layer: ``models`` (``transformer``, ``resnet``).  Device self time in dot
and convolution operations, fused or not, over device busy time in the traced
steps."""
from benchmarks import reduce


def read(run):
    if not run.trace:
        return None
    return run.trace.share_of_busy(
        lambda ev: reduce.op_class(ev) == "matmul")
