"""Layer: ``parallel.distributed``.  Self time under ``apex.ddp_allreduce``
(``DistributedDataParallel.allreduce_grads``: flatten, casts, pre- and
post-scaling and the collectives themselves) over busy time.  Only a cell on
several chips has it."""
from benchmarks import scopes


def read(run):
    names = run.chips > 1 and scopes.seen(run)
    if not names:
        return None
    return scopes.share(run.trace, scopes.under("apex.ddp_allreduce"), names)
