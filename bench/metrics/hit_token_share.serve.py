"""Share of the window's prompt tokens that the engines took from a
stored prefix fragment instead of computing (sum of each served
sequence's ``reused`` over the sum of its prompt tokens), in percent."""


def read(ctx):
    if "prompt_tokens" not in ctx or not ctx["prompt_tokens"]:
        return None
    return 100.0 * ctx["reused"] / ctx["prompt_tokens"]
