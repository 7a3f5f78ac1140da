"""Layer: ``models``.  Self time of the instructions jax marks as
``rematted_computation`` — the second forward that ``jax.checkpoint`` runs in
the backward pass of every layer — over busy time."""
from benchmarks import scopes


def read(run):
    names = scopes.seen(run)
    if not names:
        return None
    return scopes.share(
        run.trace, lambda ev, path: scopes.phase(path) == "recompute", names)
