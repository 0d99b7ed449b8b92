"""Mean wall time of an engine step that ran a prefill chunk
(``InstanceEngine.step``, timed from outside the engine), in ms."""


def read(ctx):
    walls = [s["wall"] for s in ctx.get("steps", ()) if s["kind"] == "prefill"]
    return 1e3 * sum(walls) / len(walls) if walls else None
