"""Layer: ``parallel.expert``.  The part of ``latent_moe_time_share`` that
is routing: self time under ``apex.moe`` and under none of ``apex.experts``,
``apex.latent``, ``apex.shared_expert``, and no grouped-product kernel — the
norm, the router (``apex.router``), the top-k, the sorts, the gathers into
the buffer and the token sums out of it, forward and reverse — over busy
time."""
from benchmarks import routing, scopes

_PRODUCTS = ("apex.experts", "apex.latent", "apex.shared_expert")


def read(run):
    names = scopes.seen(run)
    if not names:
        return None

    def routes(ev, path):
        inside = scopes.blocks(path)
        return ("apex.moe" in inside and not routing.is_grouped_product(ev)
                and not any(n in inside for n in _PRODUCTS))

    around = scopes.share(run.trace, routes, names)
    if around:
        print(f"[bench] routing around the latent experts: {around:.2f} % of "
              "busy, of it "
              f"{scopes.share(run.trace, scopes.under('apex.router'), names):.2f}"
              " under apex.router", flush=True)
    return around or None
