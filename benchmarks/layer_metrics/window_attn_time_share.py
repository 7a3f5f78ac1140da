"""Layer: ``models`` (``laguna``).  Self time under ``apex.swa`` (a
sliding-window attention mixer: its input norm, the four projections and
the gate, the QK norms, RoPE, the key-value heads' repeat, the layouts, the
band's flash kernels inside it, ``W_o`` and the residual), all phases, over
busy time."""
from benchmarks import scopes


def read(run):
    names = scopes.seen(run)
    if not names:
        return None
    return scopes.share(run.trace, scopes.under("apex.swa"), names) or None
