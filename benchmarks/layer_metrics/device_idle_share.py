"""Layer: device.  1 minus the union of all device-operation intervals over
the traced window, mean over chips."""


def read(run):
    if not run.trace:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
