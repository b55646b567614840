"""device_idle.serve: 1 - (union of the device's operation intervals)
/ traced window, averaged over the cell's devices, in %.  The result's
``breakdown`` attributes the idle time to the harness span it fell in:
``step`` (inside the engine's step: its host scheduling and syncs),
``submit``, ``account`` (the harness's token stamping), ``wait``
(waiting for the next arrival)."""


def read(run):
    if run.trace is None or "tracks" not in run.data:
        return None
    return 100.0 * run.trace.idle_share()
