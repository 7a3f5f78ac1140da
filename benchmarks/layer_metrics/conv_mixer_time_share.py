"""Layer: ``models``.  Self time under ``apex.conv`` (a conv layer's norm,
input projection, gates, the causal three-tap convolution, output projection
and residual), all phases, over busy time."""
from benchmarks import scopes


def read(run):
    names = scopes.seen(run)
    if not names:
        return None
    return scopes.share(run.trace, scopes.under("apex.conv"), names) or None
