"""ttft_p95_ms: 95th percentile (numpy's linear interpolation) over every
request that arrived in the window, of the time from when it was due to
the end of the step that produced its first token."""

import numpy as np


def read(run):
    if "tracks" not in run.data:
        return None
    ttft = [tr.times[0] - tr.due for tr in run.data["tracks"]
            if tr.in_window and tr.times]
    return float(np.percentile(ttft, 95)) * 1e3 if ttft else None
