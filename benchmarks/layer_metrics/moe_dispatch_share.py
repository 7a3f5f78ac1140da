"""Layer: ``parallel.expert``.  Self time under ``apex.moe`` that is NOT
under ``apex.experts``: the norm, the router (``apex.router``), the top-k,
the two sorts, the gather into the buffer and the weighted combine out of it
— what routing costs around the products it feeds — over busy time."""
from benchmarks import scopes


def read(run):
    names = scopes.seen(run)
    if not names:
        return None
    around = scopes.share(
        run.trace, lambda ev, path: "apex.moe" in scopes.blocks(path)
        and "apex.experts" not in scopes.blocks(path), names)
    router = scopes.share(run.trace, scopes.under("apex.router"), names)
    if around:
        print(f"[bench] routing around the experts: {around:.2f} % of busy, "
              f"of it {router:.2f} under apex.router", flush=True)
    return around or None
