"""Layer: ``models`` (``nemotron_h``).  Self time under ``apex.ssm`` (a
Mamba-2 layer's norm, input projection, causal convolution, the scan, the
gated group norm, output projection and residual), all phases, over busy
time."""
from benchmarks import scopes


def read(run):
    names = scopes.seen(run)
    if not names:
        return None
    return scopes.share(run.trace, scopes.under("apex.ssm"), names) or None
