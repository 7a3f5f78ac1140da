"""Layer: ``parallel.expert``.  ``moe_time_share`` in a latent expert layer:
self time under ``apex.moe`` — here also the latent projections
(``apex.latent``) and the shared expert (``apex.shared_expert``) — plus the
grouped-product kernels no scope reaches, all phases, over busy time."""


def read(run):
    return run.metric("moe_time_share")
