"""Share of the held experts that a decode step's tokens picked, over the
window's decode steps and MoE layers: ``engine.moe_experts_touched`` over
``engine.moe_layer_steps`` times the experts held, in percent.  Needs the
steps' MoE counter deltas (``serve_moe`` with ``--trace 1``)."""


def read(ctx):
    m = [s["moe"] for s in ctx.get("steps", ())
         if s["kind"] == "decode" and "moe" in s]
    layer_steps = sum(x["layer_steps"] for x in m)
    if not layer_steps:
        return None
    held = ctx["config"]["n_experts_held"]
    return 100.0 * sum(x["touched"] for x in m) / (layer_steps * held)
