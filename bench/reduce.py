"""Reduce a JAX profiler trace to device busy and idle time, time per
device operation, and the longest idle gaps with what the host was doing.

Reads the ``.xplane.pb`` file that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``: nothing but JAX.  The device's operations are
the events of the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane, named
by their HLO instruction and nested (a ``while`` holds its body's ops); the
host's are every line of ``/host:CPU``.  Device and host events share one
clock in the file.  The window is the host span named ``window`` (the
benchmark's own ``TraceAnnotation``); only what lies inside it counts.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def short_name(op: str) -> str:
    """An XLA op event is named by its whole HLO instruction
    (``%ssd_scan.1 = (bf16[...]) custom-call(...)``): keep the name."""
    return op.split(" = ", 1)[0].lstrip("%")


def _events(line, short=False) -> List[Tuple[str, float, float]]:
    name = short_name if short else (lambda n: n)
    return [(name(e.name), float(e.start_ns), float(e.start_ns) + float(e.duration_ns)) for e in line.events]


def self_times(evs) -> Dict[str, float]:
    """Seconds per op name, each op's time less that of the ops nested in
    it (a ``while`` holds the ops of its body)."""
    out: Dict[str, float] = collections.Counter()
    stack: list = []
    for n, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack and e <= stack[-1][2]:
            out[stack[-1][0]] -= (e - s) / 1e9
        out[n] += (e - s) / 1e9
        stack.append((n, s, e))
    return out


def load(path: str) -> Tuple[Dict[str, list], list]:
    """({device plane name: [(op, start_ns, end_ns)]}, [host (name, start, end)])."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(_events(line, short=True))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(_events(line))
    return devices, host


def union(intervals) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def label_gap(host, lo: float, hi: float, skip: str) -> str:
    """The innermost host event that covers the gap's middle, else the one
    that overlaps it most."""
    mid = (lo + hi) / 2
    covering = [(e - s, n) for n, s, e in host if s <= mid <= e and n != skip]
    if covering:
        return min(covering)[1]
    over = [(min(e, hi) - max(s, lo), n) for n, s, e in host if e > lo and s < hi and n != skip]
    return max(over)[1] if over else "host: nothing traced"


def reduce_profile(path: str, window: str, top: int = 10, loaded=None) -> dict:
    """busy_s and window_s (averaged over the device planes), seconds per
    device operation, and the ``top`` longest idle gaps labelled."""
    devices, host = loaded if loaded is not None else load(path)
    spans = [(s, e) for n, s, e in host if n == window]
    if not spans:
        raise ValueError(f"no host span named {window!r} in {path}")
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    if not devices:
        raise ValueError(f"no {DEVICE_PREFIX}* plane with an {OPS_LINE!r} line in {path}")
    busy, ops, gaps = [], collections.Counter(), []
    for name, evs in devices.items():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs if e > lo and s < hi]
        merged = union((s, e) for _, s, e in inside)
        busy.append(sum(e - s for s, e in merged))
        ops.update(self_times(inside))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / len(busy) / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "ops_s": dict(ops),
        "device_ops": [[n, s] for n, s in ops.most_common(top)],
        "idle_gaps": [[label_gap(host, a, b, window), d / 1e9] for d, a, b in gaps[:top]],
        "summary": f"busy {busy_s:.6f} s of {window_s:.6f} s on {len(devices)} device(s), "
                   f"{sum(len(v) for v in devices.values())} device events",
    }


def kernel_seconds(profile: dict, name: str) -> Optional[float]:
    """Summed device time of the kernel ``name`` (ops ``name``, ``name.1``, ...)."""
    total = sum(s for n, s in profile["ops_s"].items() if n.split(".")[0] == name)
    return total or None
