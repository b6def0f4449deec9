"""Output tokens served in the window over the window's seconds."""


def read(ctx):
    return ctx["tokens_out"] / ctx["window_s"]
