"""batch_occupancy: mean number of lanes that a decode step of the window
advanced (tokens the engine's decode emitted), over the engine's
``max_lanes``, in %.  Counted by the harness from the requests' outputs
after every step."""


def read(run):
    if "steps" not in run.data:
        return None
    t0 = run.data["t0"]
    busy = [len(st.decode_ctx) for st in run.data["steps"]
            if t0 <= st.t0 < t0 + run.window_s and st.decode_ctx]
    if not busy:
        return None
    return 100.0 * sum(busy) / len(busy) / run.data["lanes"]
