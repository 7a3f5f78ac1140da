"""Layer: entry_loop (the example's step function and jit dispatch).
Median host time of one non-blocking ``step(state, batch)`` call in the
measured window: the batch's ``device_put`` plus the enqueue.  It moves
``samples_per_s`` only once ``device_idle_share`` is no longer about 0."""
import statistics


def read(run):
    if not run.on_chip or not run.dispatch_ms:
        return None
    return statistics.median(run.dispatch_ms)
