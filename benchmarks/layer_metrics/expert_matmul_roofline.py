"""Layer: ``parallel.expert``.  The grouped products' share of their
roofline: the least time the chip could take for the rows the held experts
WERE SENT (``routing.ring_rows``: the program's routing of the traced
batches, not the expectation — an implementation that multiplies the whole
buffer reads an eighth) over the time the grouped-product kernels took in the
traced steps.  ``flops_lfm2.grouped_ffn_cost`` a layer and pass; a forward
pass is two kernel calls and a backward four, so the trace says how often
the forward ran (twice under remat)."""
from benchmarks import flops, flops_lfm2, routing


def read(run):
    shape = run.job.facts.get("experts")
    rows = routing.ring_rows(run)
    if not run.trace or not shape or not rows:
        return None
    dev = run.trace.devices[0]
    spent_ns = dev.self_ns(routing.is_grouped_product)
    calls = dev.count(routing.is_grouped_product)
    if not spent_ns:
        return None
    # every step of the trace reads one batch of the ring, in turn
    traced = [rows[i % len(rows)] for i in range(run.trace.n_steps)]
    passes = calls / (run.trace.n_steps * shape["layers"])
    forwards = (passes - 4) / 2
    least_s = 0.0
    for step in traced:
        for layer in step:
            for kind, times in (("fwd", forwards), ("bwd", 1)):
                least_s += times * flops.roofline_seconds(
                    *flops_lfm2.grouped_ffn_cost(
                        int(layer.sum()), shape["held"], shape["d_model"],
                        shape["d_ff"], kind, shape["itemsize"]),
                    run.peaks)[0]
    print(f"[bench] grouped products: {calls} kernel calls in "
          f"{run.trace.n_steps} steps x {shape['layers']} layers (forward x"
          f"{forwards:g}, backward x1), "
          f"{sum(int(s.sum()) for s in traced)} rows sent to held experts; "
          f"least {least_s * 1e3:.2f} ms, took {spent_ns / 1e6:.2f} ms",
          flush=True)
    return 100.0 * least_s * 1e9 / spent_ns
