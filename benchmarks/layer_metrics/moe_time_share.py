"""Layer: ``parallel.expert``.  Self time of the instructions under
``apex.moe`` (the FFN's norm, the router, the sorts and gathers of the
dispatch, the gate between the grouped products, the weighted combine) and
of the grouped products themselves (``routing.is_grouped_product``: XLA
names that kernel, no scope reaches it), all phases, over busy time in the
traced steps."""
from benchmarks import routing, scopes


def read(run):
    names = scopes.seen(run)
    if not names:
        return None
    return scopes.share(
        run.trace, lambda ev, path: "apex.moe" in scopes.blocks(path)
        or routing.is_grouped_product(ev), names) or None
