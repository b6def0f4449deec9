"""Set-up: process start to the window's start (weights, compiles or the
compile cache, warm-up, ramp)."""


def read(ctx):
    return ctx["setup_s"]
