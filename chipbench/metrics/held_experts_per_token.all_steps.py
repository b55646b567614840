"""held_experts_per_token.all_steps: held experts a decoded token is
routed to in one MoE layer, over every step the serve driver counts (the
closed loop's warm-up steps, the window and the drain): the growth of the
engine's ``moe_held_assignments`` counter (the (token, held expert)
assignments of active lanes, summed over MoE layers) between the
driver's two readings of the engine's counters (``run.data["stats"]``),
over the tokens those steps decoded (``run.data["steps"]``) and the MoE
layers of the work counts (``run.data["lm"]``).  A uniform router gives
top_k x held / router experts (6 x 8 / 64 = 0.75 for DeepSeek-V2-Lite's
share).  None where the engine keeps no such counter."""


def read(run):
    stats = run.data.get("stats")
    lm = run.data.get("lm")
    if not stats or not hasattr(lm, "moe_layers"):
        return None
    a, b = stats["start"], stats["end"]
    tokens = sum(len(st.decode_ctx) for st in run.data.get("steps", ()))
    if "moe_held_assignments" not in a or not tokens:
        return None
    held = b["moe_held_assignments"] - a["moe_held_assignments"]
    return held / (tokens * lm.moe_layers)
