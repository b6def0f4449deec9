"""THAPI events recorded in the window (``TraceHandle.events``) per output
token served."""


def read(ctx):
    return ctx["thapi"]["events"] / ctx["tokens_out"]
