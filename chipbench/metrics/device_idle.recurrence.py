"""device_idle.recurrence: 1 - (union of the device's operation intervals)
/ traced window, averaged over the cell's devices, in %."""


def read(run):
    if run.trace is None or "ops_per_call" not in run.data:
        return None
    return 100.0 * run.trace.idle_share()
