"""Layer: ``models`` (``glm4_moe_lite``).  Self time under ``apex.mla`` (a
latent-attention mixer: its input norm, the four projections, the two
latent norms, RoPE, the key's assembly, the layout copies, the flash
kernels inside it, ``W_o`` and the residual), all phases, over busy time.
The MTP module's mixer is counted too: ``mtp_time_share`` overlaps it."""
from benchmarks import scopes


def read(run):
    names = scopes.seen(run)
    if not names:
        return None
    return scopes.share(run.trace, scopes.under("apex.mla"), names) or None
