"""Layer: ``optimizers``.  Bytes the update must read and write
(``flops.optimizer_update_bytes`` over the cell's parameters) over the time
``optimizer_step_ms`` measured, as a share of the chip's HBM bandwidth."""


def read(run):
    ms = run.metric("optimizer_step_ms")
    if ms is None:
        return None
    rate = run.job.facts["optimizer_bytes"] / (ms / 1e3)
    return 100.0 * rate / run.peaks["hbm_bytes_per_s"]
