"""Layer: ``models`` (``qwen3_next``).  Self time under ``apex.gdn`` (a Gated
DeltaNet mixer: norm, the two input projections, the causal convolution, the
delta rule, the gated norm, output projection and residual), all phases, over
busy time."""
from benchmarks import scopes


def read(run):
    names = scopes.seen(run)
    if not names:
        return None
    return scopes.share(run.trace, scopes.under("apex.gdn"), names) or None
