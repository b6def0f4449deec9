"""Share of the profiled window in which the device was idle while the
serving thread was inside ``engine_step`` but in no ``dispatch``: slot filling, the
splice, readbacks, bookkeeping
(``bench/attribute.py`` splits ``device_idle.serve`` four ways)."""

from bench.attribute import idle_share


def read(ctx):
    return idle_share(ctx, "engine")
