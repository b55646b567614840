"""itl_p95_ms: 95th percentile (numpy's linear interpolation) over all
gaps between consecutive output tokens of every request that arrived in
the window, the gaps after the window's close included."""

import numpy as np


def read(run):
    if "tracks" not in run.data:
        return None
    gaps = [b - a for tr in run.data["tracks"] if tr.in_window
            for a, b in zip(tr.times, tr.times[1:])]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
