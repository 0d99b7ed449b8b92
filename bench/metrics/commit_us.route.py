"""Mean host time of the router's commit stage per wave in the window:
``RoutingPipeline.commit_ns`` (per-request hooks, KV index inserts and
evictions, and any sequential re-route after a mid-wave eviction) over
waves, in microseconds."""


def read(ctx):
    st = ctx.get("stage")
    if not st or not st["waves"]:
        return None
    return st["commit_ns"] / st["waves"] / 1e3
