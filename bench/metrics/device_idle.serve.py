"""Share of the profiled window in which no operation ran on the device
(1 − union of the device's op intervals ÷ window), from the profiler."""


def read(ctx):
    p = ctx.get("profile")
    return None if p is None else 100.0 * (1.0 - p["busy_s"] / p["window_s"])
