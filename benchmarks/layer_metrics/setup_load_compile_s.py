"""Layer: entry_loop.  Seconds of set-up under ``compile.backend_compile``:
loads from the persistent cache where it is warm, XLA's compilations where it
is cold (the ``[bench] set-up:`` line says how many of each), up to the end
of set-up (``benchmarks/setup_record.py``).  A time, so on the chip only."""
from benchmarks import setup_record


def read(run):
    return setup_record.seconds(run, "load_compile_s")
