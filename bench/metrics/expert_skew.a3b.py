"""How unevenly a decode step's choices fall on the held experts it
touches, over the window's decode steps and MoE layers: the mean of the
most choices one held expert took (``engine.moe_max_expert_tokens`` over
``engine.moe_layer_steps``) over the mean choices per touched held
expert (``engine.moe_routed_local`` over ``engine.moe_experts_touched``);
1 is even.  Needs the steps' MoE counter deltas (``serve_moe`` with
``--trace 1``)."""


def read(ctx):
    m = [s["moe"] for s in ctx.get("steps", ())
         if s["kind"] == "decode" and "moe" in s]
    touched = sum(x["touched"] for x in m)
    if not touched:
        return None
    most = sum(x["max_tokens"] for x in m) / sum(x["layer_steps"] for x in m)
    return most / (sum(x["routed"] for x in m) / touched)
