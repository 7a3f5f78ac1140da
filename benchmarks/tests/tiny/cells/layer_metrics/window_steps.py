"""A per-layer metric added from outside the harness's own directory: the
number of steps the measured window ran.  It exists to show that a new reader
is a new file and a ``BENCHMARK.json`` entry, nothing else."""


def read(run):
    return float(run.steps)
