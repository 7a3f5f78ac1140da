"""Layer: ``optimizers``.  Self time of the instructions under
``apex.amp_step`` (unscale and finite check, the optimizer's update with its
skip select, the model-precision copy) over busy time, in the traced steps:
the update's share INSIDE the step, where ``optimizer_step_ms`` times it
alone from outside."""
from benchmarks import scopes


def read(run):
    names = scopes.seen(run)
    if not names:
        return None
    return scopes.share(run.trace, scopes.under("apex.amp_step"), names)
