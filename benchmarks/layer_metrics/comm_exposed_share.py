"""Layer: ``parallel.distributed``.  Collective time that no compute on the
same chip covers (``reduce.DeviceWindow.exposed_comm_ns``), mean over chips,
over the traced window.  Only a cell on several chips has it."""


def read(run):
    if not run.trace or run.chips == 1:
        return None
    return 100.0 * run.trace.mean(
        lambda d: d.exposed_comm_ns() / d.window_ns)
