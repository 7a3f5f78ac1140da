"""Layer: entry_loop.  Seconds under ``setup.import``, the program's span
from the first to the last line of ``apex_tpu/__init__.py``: the program's
and jax's part of the seconds a run takes to its first line
(``benchmarks/setup_record.py``).  A time, so on the chip only."""
from benchmarks import setup_record


def read(run):
    return setup_record.seconds(run, "import_s")
