"""Layer: entry_loop.  Seconds of set-up the host spent tracing Python and
lowering (Mosaic's lowering of the kernels included) — what no compile cache
saves: the union of the set-up record's ``compile.jaxpr_trace`` and
``compile.jaxpr_to_mlir_module`` intervals up to the end of set-up, less what
a build inside them covers (``benchmarks/setup_record.py``).  A time, so on
the chip only."""
from benchmarks import setup_record


def read(run):
    return setup_record.seconds(run, "trace_lower_s")
