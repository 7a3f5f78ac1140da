"""Layer: ``models`` (``qwen3_next``).  The gated delta rule's share of its
roofline: the least time the chip could take for the rule's work
(``flops_qwen3_next.gated_delta_rule_cost``: the FLOPs of the recurrence;
``q``, ``k``, ``v``, ``g``, ``β`` read and ``o`` written once a pass, the
backward reading ``do`` too and writing their gradients — the SAME whatever
implements it) over the self time under ``apex.gdn_rule`` (decays, the
triangular system, the chunk loop; the projections, convolution and norms
around it are ``apex.gdn``'s) in the traced steps.  Which passes ran is read
from the trace: a forward pass for the forward phase and one more where remat
recomputed it, one backward."""
import collections

from benchmarks import flops, flops_qwen3_next, scopes

_PASSES = (("forward", "fwd"), ("recompute", "fwd"), ("backward", "bwd"))


def read(run):
    shape = run.job.facts.get("gdn")
    names = scopes.seen(run)
    if not names or not shape:
        return None
    spent_ns = collections.Counter()
    for ev, ns in run.trace.devices[0].selfs:
        path = scopes.path_of(ev, names)
        if "apex.gdn_rule" in scopes.blocks(path):
            spent_ns[scopes.phase(path)] += ns
    if not spent_ns:
        return None
    least_s = {phase: run.trace.n_steps * shape["layers"]
               * flops.roofline_seconds(
                   *flops_qwen3_next.gated_delta_rule_cost(
                       shape["tokens"], shape["heads"], shape["key_heads"],
                       shape["key_dim"], shape["value_dim"], passes,
                       shape["itemsize"]), run.peaks)[0]
               for phase, passes in _PASSES if spent_ns[phase]}
    print("[bench] delta rule: " + "; ".join(
        f"{phase} least {least_s[phase] * 1e3:.2f} ms, took "
        f"{spent_ns[phase] / 1e6:.2f} ms" for phase in least_s)
        + f" in {run.trace.n_steps} steps x {shape['layers']} layers",
        flush=True)
    return 100.0 * sum(least_s.values()) * 1e9 / sum(spent_ns.values())
