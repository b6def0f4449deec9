"""95th percentile of the window's THAPI ``queue_wait`` spans: a request's
time from ``submit`` to the start of its prefill."""

import statistics


def read(ctx):
    waits = ctx.get("queue_wait_s")
    if not waits or len(waits) < 2:
        return None
    return statistics.quantiles(waits, n=100, method="inclusive")[94] * 1e3
