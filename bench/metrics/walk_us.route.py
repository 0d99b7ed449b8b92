"""Mean host time of the router's walk stage per wave in the window:
``RoutingPipeline.walk_ns`` (aggregated-index walks and the wave's
pairwise LCP) over waves, in microseconds."""


def read(ctx):
    st = ctx.get("stage")
    if not st or not st["waves"]:
        return None
    return st["walk_ns"] / st["waves"] / 1e3
