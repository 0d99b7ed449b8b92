"""Mean time of the router's score stage per wave in the window:
``RoutingPipeline.score_ns`` (the wave program's dispatch, the device
run and the plan's copy back to the host) over waves, in microseconds."""


def read(ctx):
    st = ctx.get("stage")
    if not st or not st["waves"]:
        return None
    return st["score_ns"] / st["waves"] / 1e3
