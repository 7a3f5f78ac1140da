"""Layer: entry_loop.  Programs jax built or loaded up to the end of set-up:
the ``compile.backend_compile`` entries of the program's set-up record up to
the last entry of the step's program (``benchmarks/setup_record.py``) — the
step, the state's program and every operation run outside any ``jit``.  Each
costs a trace, a lowering and a look-up, a read and a deserialisation.  A
count from the program's own record, so it is read off the chip too."""
from benchmarks import setup_record


def read(run):
    record = setup_record.record(run)
    return None if record is None else float(record["programs"])
