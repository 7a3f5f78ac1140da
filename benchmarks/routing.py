"""What the routed-expert readers share: which trace events are the grouped
products, and the rows the held experts are sent.

*The kernel.*  On a TPU XLA turns ``jax.lax.ragged_dot`` into a kernel of its
own and names the instruction itself (``%ragged-dot-none.7 = ...
custom-call(...)``, ``op_name="ragged-dot-none"``): the program's scopes do
not reach it (seen on the chip, PR 27: 4.7 % of busy time "outside every
block"), so it is told by its name, as the flash kernels are.  Beside it
runs ``%ragged-dot-metadata``, a few microseconds that turn the group sizes
into the kernel's tile table: not a product, and not counted as one.

*The rows.*  The program has a routing meter inside the step
(``telemetry.events.record_expert_rows``, a host callback), and the
benchmark does not turn it on: the profiler's trace of a TPU program that
holds a host callback carries no ``Hlo Proto`` of it (seen on the chip, PR
27: ``/host:metadata`` then holds one two-instruction program), and every
``*_share`` read from scopes would fall silent.  So the rows come from the
program's own routing code outside the step: the job's ``routing_probe``
runs ``models.lfm2.lfm2_routing`` over each batch of the ring on the
parameters as the traced steps left them.  The traced steps are the ring
once over (8 batches, 8 steps); the parameters moved by a few LAMB steps of
0.1 % since, which moves a handful of assignments in 131 072.
"""
from __future__ import annotations

from benchmarks import reduce

KERNEL = "ragged-dot"


def is_grouped_product(event) -> bool:
    name = reduce.base_name(event.name)
    return name.startswith(KERNEL) and "metadata" not in name


def ring_rows(run) -> list:
    """``[(expert layers, held) int array]``, one for each batch of the
    ring; [] where the job has no routed experts.  Probed once a run."""
    probe = run.job.facts.get("routing_probe")
    if probe is None:
        return []
    if not hasattr(run, "ring_rows"):
        rows, dropped = probe(run.state, run.job.batches)
        print(f"[bench] routing probe over the ring: rows sent to held "
              f"experts a batch {[int(r.sum()) for r in rows]}, dropped "
              f"{dropped}", flush=True)
        run.ring_rows = rows
    return run.ring_rows
