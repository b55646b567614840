"""recurrence_tops: 2*M*N*K of every call completed in the window, over
the window's length (host clock), in TOP/s."""


def read(run):
    if "ops_per_call" not in run.data:
        return None
    return run.data["calls"] * run.data["ops_per_call"] / run.window_s / 1e12
