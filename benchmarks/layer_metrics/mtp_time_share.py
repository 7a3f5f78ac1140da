"""Layer: ``models`` (``glm4_moe_lite``).  Self time under ``apex.mtp`` (the
multi-token-prediction module: the join's two norms and ``W_eh``, its block
— ``apex.mla`` and ``apex.moe`` inside —, its pass through the head and its
loss term), all phases, over busy time.  It overlaps ``mla_time_share``,
``moe_time_share`` and ``head_loss_time_share`` by design."""
from benchmarks import scopes


def read(run):
    names = scopes.seen(run)
    if not names:
        return None
    return scopes.share(run.trace, scopes.under("apex.mtp"), names) or None
