"""95th percentile of the time to first token over every request first
served in the window: from ``submit`` to the return of the engine step that
holds the request's first token."""

import statistics


def read(ctx):
    return statistics.quantiles(ctx["ttft_s"], n=100, method="inclusive")[94] * 1e3
