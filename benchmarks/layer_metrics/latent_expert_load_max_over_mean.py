"""Layer: ``parallel.expert``.  ``expert_load_max_over_mean`` of a latent
expert layer: the fullest held expert's rows over the mean, worst layer and
batch of the ring, from the job's routing probe (a count, read off the chip
too)."""


def read(run):
    return run.metric("expert_load_max_over_mean")
