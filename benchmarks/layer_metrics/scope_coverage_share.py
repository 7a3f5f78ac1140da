"""Layer: device.  Self time of the traced instructions whose ``op_name``
holds any of the program's ``apex.*`` scopes, over busy time: the check on
every other ``*_share`` read from scopes.  It logs the block x phase table of
the run (``scopes.table``) once, how much of the covered time lies in fusions
that XLA named itself and that are read by what they fuse
(``scopes.module_paths``), and what the time outside every block is.
None — never 0 — where no instruction has a scope: the step was then compiled
before the program had scopes (the parent's tree, or a cache entry keyed
without its metadata)."""
from benchmarks import reduce, scopes


def read(run):
    if not run.trace:
        return None
    names = scopes.names_of(run)
    covered = names and scopes.share(
        run.trace, lambda ev, path: bool(scopes.blocks(path)), names)
    if not covered:
        print("[bench] no instruction of the trace carries an apex.* scope ("
              + (f"{len(names.paths)} instructions read from the trace's own "
                 "program): the step was compiled before the program had "
                 "scopes" if names else f"no file under {scopes.TRACE_DIR} "
                 "holds this run's trace and its program)")
              + "; the metrics read from scopes are left out", flush=True)
        return None
    renamed = run.trace.share_of_busy(
        lambda ev: reduce.instruction(ev.name)[0] in names.renamed)
    print("[bench] block x phase, % of device busy time:\n"
          + scopes.format_table(scopes.table(run.trace, names))
          + f"\n[bench] under a block: {covered:.2f}, of it {renamed:.2f} in "
          "fusions XLA named, read by what they fuse"
          + "\n[bench] outside every block: " + "; ".join(
              f"{label} {value:.2f}"
              for label, value in scopes.unscoped_rows(run.trace, names)),
          flush=True)
    return covered
