"""Share of the profiled window in which the device was idle while the
serving thread was inside a jit ``dispatch`` span and not its fence: the host
enqueueing the program (arguments, allocation)
(``bench/attribute.py`` splits ``device_idle.serve`` four ways)."""

from bench.attribute import idle_share


def read(ctx):
    return idle_share(ctx, "dispatch")
