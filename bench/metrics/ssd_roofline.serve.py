"""Least time of the profiled window's SSD scans on the chip (the larger of
FLOPs over peak and bytes over bandwidth, ``bench/flops.py``) over the
device time of the ``ssd_scan`` kernel in the profiler's trace."""

from bench.flops import ssd_min_time
from bench.reduce import kernel_seconds


def read(ctx):
    p, calls = ctx.get("profile"), ctx["ssd_calls"]
    if p is None or not calls:
        return None
    spent = kernel_seconds(p, "ssd_scan")
    if spent is None:
        return None
    pk = ctx["peaks"]
    least = sum(n * ssd_min_time(S, ctx["dims"], pk.flops, pk.hbm_bw) for S, n in calls)
    return 100.0 * least / spent
