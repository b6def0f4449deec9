"""Share of the profiled window in which the device was idle while the
serving thread was inside a fence, ``block_until_ready``: its wake-up, full mode's
``poll_ready`` spin
(``bench/attribute.py`` splits ``device_idle.serve`` four ways)."""

from bench.attribute import idle_share


def read(ctx):
    return idle_share(ctx, "fence")
