"""output_tok_s: output tokens emitted inside the window, over its length
(host clock).  A token is stamped with the end of the step that made it."""


def read(run):
    if "tracks" not in run.data:
        return None
    t0 = run.data["t0"]
    t1 = t0 + run.window_s
    n = sum(1 for tr in run.data["tracks"] for t in tr.times if t0 <= t <= t1)
    return n / run.window_s
