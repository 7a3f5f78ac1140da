"""Layer: ``models`` (``nemotron_h``).  The state-space scan's share of its
roofline: the least time the chip could take for the scan's work
(``flops_nemotron_h.ssd_scan_cost``: the FLOPs of the recurrence; ``x``,
``B``, ``C``, ``Δ`` read and ``y`` written once a pass, the backward reading
``dy`` too and writing their gradients — the SAME whatever implements it)
over the self time under ``apex.ssm_scan`` (discretisation and the scan; the
projections, convolution and norms around it are ``apex.ssm``'s) in the traced
steps.  Which passes ran is read from the trace: a forward pass for the
forward phase and one more where remat recomputed it, one backward."""
import collections

from benchmarks import flops, flops_nemotron_h, scopes

_PASSES = (("forward", "fwd"), ("recompute", "fwd"), ("backward", "bwd"))


def read(run):
    shape = run.job.facts.get("ssm")
    names = scopes.seen(run)
    if not names or not shape:
        return None
    spent_ns = collections.Counter()
    for ev, ns in run.trace.devices[0].selfs:
        path = scopes.path_of(ev, names)
        if "apex.ssm_scan" in scopes.blocks(path):
            spent_ns[scopes.phase(path)] += ns
    if not spent_ns:
        return None
    least_s = {phase: run.trace.n_steps * shape["layers"]
               * flops.roofline_seconds(*flops_nemotron_h.ssd_scan_cost(
                   shape["tokens"], shape["heads"], shape["head_dim"],
                   shape["groups"], shape["state"], passes,
                   shape["itemsize"]), run.peaks)[0]
               for phase, passes in _PASSES if spent_ns[phase]}
    print("[bench] scan: " + "; ".join(
        f"{phase} least {least_s[phase] * 1e3:.2f} ms, took "
        f"{spent_ns[phase] / 1e6:.2f} ms" for phase in least_s)
        + f" in {run.trace.n_steps} steps x {shape['layers']} layers",
        flush=True)
    return 100.0 * sum(least_s.values()) * 1e9 / sum(spent_ns.values())
