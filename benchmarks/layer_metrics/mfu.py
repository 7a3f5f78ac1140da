"""Layer: device.  Model FLOPs utilization: the FLOPs the forward and
backward passes require per sample (``flops.py``; recompute not counted)
times the window's samples per second, over chips times the bf16 peak."""


def read(run):
    if not run.on_chip:
        return None
    rate = run.job.flops_per_sample * run.samples_per_s
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops_per_s"])
