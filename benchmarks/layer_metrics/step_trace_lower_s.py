"""Layer: entry_loop.  The part of ``setup_trace_lower_s`` under the step's
own program: the seconds from entering the first call of the step to handing
its module to the compiler or the cache, whatever it traces inside — kernels
traced anew a layer, wrappers that are no ``jit`` — less the builds of
operations it runs eagerly on the way (``benchmarks/setup_record.py``).  A
time, so on the chip only."""
from benchmarks import setup_record


def read(run):
    return setup_record.seconds(run, "step_trace_lower_s")
