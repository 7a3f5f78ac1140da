"""Layer: ``optimizers`` (+ ``multi_tensor_apply``).  The jitted
``amp.amp_step(state, grads)`` alone on the cell's own state, after the
window: median of ten calls, each waited for.  Timed from outside on purpose
— the step has no named scopes yet; a span inside the step replaces this
reader once the program has one."""


def read(run):
    update, state, grads = run.job.optimizer_probe(run.state)
    seconds, _ = run.time_blocked(update, state, grads)
    return None if seconds is None else seconds * 1e3
