"""Layer: ``models``.  Self time under ``apex.head`` (final norm, vocabulary
projection) or ``apex.loss`` (cross-entropy over the logits at every
position), forward and backward, over busy time."""
from benchmarks import scopes


def read(run):
    names = scopes.seen(run)
    if not names:
        return None
    return scopes.share(run.trace, scopes.under("apex.head", "apex.loss"),
                        names)
