"""Share of the profiled window in which the device was idle while the
serving thread was outside any ``engine_step``: the clients' loop between steps
(``bench/attribute.py`` splits ``device_idle.serve`` four ways)."""

from bench.attribute import idle_share


def read(ctx):
    return idle_share(ctx, "caller")
