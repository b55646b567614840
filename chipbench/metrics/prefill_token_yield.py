"""prefill_token_yield: real prompt tokens prefilled over the rows the
prefill buckets computed for them, in %, summed over every request that
arrived in the window (re-admissions after preemption included).  Read
from the engine's per-request counts ``prefill_tokens`` and
``prefill_padded_tokens``; None where the engine keeps neither."""


def read(run):
    if "tracks" not in run.data:
        return None
    reqs = [tr.req for tr in run.data["tracks"] if tr.in_window]
    if not reqs or not hasattr(reqs[0], "prefill_padded_tokens"):
        return None
    padded = sum(r.prefill_padded_tokens for r in reqs)
    if padded <= 0:
        return None
    return 100.0 * sum(r.prefill_tokens for r in reqs) / padded
