"""Least time of the profiled window's prefill attention calls on the chip
(the larger of FLOPs over peak and bytes over bandwidth, causal GQA,
``bench/flops_hybrid.py``) over the device time of the ``flash_attention``
kernel in the profiler's trace."""

from bench.flops_hybrid import flash_min_time
from bench.reduce import kernel_seconds


def read(ctx):
    p, calls = ctx.get("profile"), ctx.get("attn_calls")
    if p is None or not calls:
        return None
    spent = kernel_seconds(p, "flash_attention")
    if spent is None:
        return None
    pk = ctx["peaks"]
    least = sum(n * flash_min_time(S, ctx["dims"], pk.flops, pk.hbm_bw) for S, n in calls)
    return 100.0 * least / spent
