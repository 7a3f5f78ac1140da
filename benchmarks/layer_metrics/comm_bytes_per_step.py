"""Layer: ``parallel.distributed``.  Bytes one chip hands to collectives in a
step, read from what the step ran: the operand types of the collective
instructions in the trace (``reduce.DeviceWindow.comm_bytes``), mean over
chips, over the traced steps.  Reducing in float32, compressing or dropping a
collective all move it; better overlap does not — that is
``comm_exposed_share``'s to show.  The gradient tree's own size is printed
beside it: every gradient once, in its own dtype."""


def read(run):
    if not run.trace or run.chips == 1:
        return None
    nbytes = run.trace.mean(lambda d: d.comm_bytes()) / run.trace.n_steps
    print(f"[bench] collectives: {nbytes:.0f} bytes a step and chip in the "
          f"trace; the gradient tree holds "
          f"{run.job.facts.get('gradient_bytes')}", flush=True)
    return nbytes
