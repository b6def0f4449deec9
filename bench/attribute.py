"""Device idle time split by what the serving thread was doing: the JAX
profiler's trace and THAPI's trace of one window, on one clock.

    python3 bench/attribute.py --workload mamba2-serve-chat --seed 7 --seconds 50

makes one ``--trace 1`` run of the cell as ``bench/run.py`` does, with one
call more: :func:`fill`, made after the profile is reduced and while the
run's temporary directory still exists.  Its last line of standard output
is ``bench/run.py``'s result object, with the per-layer metrics of
:data:`METRICS` beside the cell's own.

:func:`fill` adds to the run's ``ctx``:

- ``thapi_dir``: the THAPI trace of the window;
- ``idle_split_s``: the device's idle seconds in the profiled window,
  averaged over the device planes like ``busy_s``, split four ways by the
  innermost THAPI span open on the serving thread (the one that records
  ``engine_step``), so that the four add up to the window less ``busy_s``:

  ``fence``     inside ``block_until_ready`` (the fence's wake-up; full
                mode's ``poll_ready`` spin);
  ``dispatch``  inside a jit ``dispatch`` and not its fence (the host
                enqueueing the program: arguments, allocation);
  ``engine``    inside ``engine_step`` and neither (slot filling, the
                splice, readbacks, bookkeeping);
  ``caller``    outside any ``engine_step`` (the clients' loop);

  None where the profile has no TPU plane or no clock anchor;
- ``consumer_drain_s``: the THAPI consumer's ``consumer_drain`` total;
- ``queue_wait_s``: each ``queue_wait`` span of the window, in seconds.

It also logs the alignment (offset, spread, anchors), the idle time under
a consumer drain, the share of the decode program's ``PjitFunction`` host
events inside the mapped ``dispatch`` spans of the decode steps, and the
window's longest ``engine_step`` with its child spans.
"""

from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import reduce  # noqa: E402

STEP = "ust_repro:engine_step"
DISPATCH = "ust_jaxrt:dispatch"
FENCE = "ust_jaxrt:block_until_ready"
DECODE = "ust_repro:decode_step"
DRAIN = "ust_repro:consumer_drain"
WAIT = "ust_repro:queue_wait"
WINDOW = "bench.window"
#: a mark whose tick recorded no consumer_drain within this is unmatched
MATCH_NS = 1_000_000

#: the per-layer metrics this module's ``ctx`` keys feed
METRICS = [
    {"name": "idle_dispatch.serve", "unit": "%", "better": "lower", "source": "device_trace",
     "layer": "jit dispatch", "moves": "serve_tok_s"},
    {"name": "idle_fence.serve", "unit": "%", "better": "lower", "source": "device_trace",
     "layer": "jit dispatch", "moves": "serve_tok_s"},
    {"name": "idle_engine.serve", "unit": "%", "better": "lower", "source": "device_trace",
     "layer": "serve engine", "moves": "serve_tok_s"},
    {"name": "idle_caller.serve", "unit": "%", "better": "lower", "source": "device_trace",
     "layer": "serve engine", "moves": "serve_tok_s"},
    {"name": "consumer_busy.serve", "unit": "%", "better": "lower", "source": "program_span",
     "layer": "rings and consumer", "moves": "serve_tok_s"},
    {"name": "queue_wait_p95_ms.serve", "unit": "ms", "better": "lower", "source": "program_span",
     "layer": "serve engine", "moves": "ttft_p95_ms"},
]


def overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_intervals(evs, lo: float, hi: float) -> list:
    """The gaps in [lo, hi] between a device plane's ops (name, start, end)."""
    merged = reduce.union((max(s, lo), min(e, hi)) for _, s, e in evs if e > lo and s < hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def split_idle(idle, step, dispatch, fence) -> dict:
    """Idle time (sorted disjoint intervals) split by the innermost of the
    nested spans fence ⊂ dispatch ⊂ step, and ``caller`` outside them all:
    the four parts add up to the idle time."""
    f = overlap(idle, reduce.union(fence))
    df = overlap(idle, reduce.union(dispatch + fence))
    sdf = overlap(idle, reduce.union(step + dispatch + fence))
    return {"dispatch": df - f, "fence": f, "engine": sdf - df,
            "caller": sum(b - a for a, b in idle) - sdf}


def marks(path: str) -> list:
    """(profile start, ``ts`` argument) of each clock mark of the host plane."""
    from jax.profiler import ProfileData

    from repro.core.clock import PROFILE_MARK

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == reduce.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == PROFILE_MARK:
                        ts = dict(e.stats).get("ts")
                        if ts is not None:
                            out.append((float(e.start_ns), int(ts)))
    return out


def anchors(mark_list, drain_starts) -> list:
    """(profile start, trace stamp before, trace stamp after) per mark: the
    stamp after is the start of the consumer_drain its tick recorded."""
    starts = sorted(drain_starts)
    out = []
    for p, before in mark_list:
        i = bisect.bisect_left(starts, before)
        if i < len(starts) and starts[i] - before <= MATCH_NS:
            out.append((p, before, starts[i]))
    return out


def idle_share(ctx: dict, part: str):
    """One part of ``idle_split_s`` as a share of the profiled window."""
    split, prof = ctx.get("idle_split_s"), ctx.get("profile")
    return None if not split or prof is None else 100.0 * split[part] / prof["window_s"]


def _spans(rows, name, tid=None, shift=0.0) -> list:
    return [(ts + shift, ts + dur + shift) for ts, dur, _, t, n, _ in rows
            if n == name and (tid is None or t == tid)]


def longest_step(rows, tid) -> str:
    """The longest engine_step on thread ``tid`` and the spans inside it."""
    steps = [(dur, ts) for ts, dur, _, t, n, _ in rows if n == STEP and t == tid]
    if not steps:
        return "no engine_step"
    dur, ts = max(steps)
    first = min(t for _, t in steps)
    inside = collections.defaultdict(lambda: [0, 0])
    for s, d, _, t, n, _ in rows:
        if t == tid and n != STEP and s >= ts and s + d <= ts + dur:
            inside[n][0] += d
            inside[n][1] += 1
    parts = ", ".join(f"{n} {v[0] / 1e6:.3f} ms ({v[1]})"
                      for n, v in sorted(inside.items(), key=lambda kv: -kv[1][0]))
    return f"longest engine_step {dur / 1e6:.3f} ms, {(ts - first) / 1e9:.3f} s after the first: {parts}"


def serving_thread(rows):
    """The thread that records ``engine_step`` (the most of them), or None."""
    tids = collections.Counter(t for _, _, _, t, n, _ in rows if n == STEP)
    return tids.most_common(1)[0][0] if tids else None


def attribute(devices, host, mark_list, rows):
    """The idle split of a profile's window (``bench.reduce.load``'s
    ``devices`` and ``host``) by the THAPI ``rows`` (``query_intervals``)
    of the serving thread, placed on the profile by the clock marks; None
    without a device plane, a clock anchor or a serving thread.  Returns
    {``split``: seconds per part averaged over the planes, ``clock``,
    ``window_s``, ``drained_s``: idle seconds under a consumer drain,
    ``decode_pjit``: (decode ``PjitFunction`` events inside a decode
    step's mapped ``dispatch``, all of them), ``anchors``}."""
    from repro.core.clock import profile_clock

    tid = serving_thread(rows)
    pairs = anchors(mark_list, [s for s, _ in _spans(rows, DRAIN)])
    clock = profile_clock(pairs)
    if not devices or clock is None or tid is None:
        return None
    win = [(s, e) for n, s, e in host if n == WINDOW]
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    off = clock.offset_ns
    step, dispatch, fence = (_spans(rows, n, tid, off) for n in (STEP, DISPATCH, FENCE))
    drains = reduce.union(_spans(rows, DRAIN, shift=off))
    split = collections.Counter()
    drained = 0.0
    for evs in devices.values():
        idle = idle_intervals(evs, lo, hi)
        split.update(split_idle(idle, step, dispatch, fence))
        drained += overlap(idle, drains)
    n = len(devices)
    decode = reduce.union(_spans(rows, DECODE, tid, off))
    in_decode = [iv for iv in dispatch if overlap([iv], decode) == iv[1] - iv[0]]
    pjit = [(s, e) for nm, s, e in host if nm.startswith("PjitFunction(decode_step") and lo <= s <= hi]
    covered = sum(any(a <= s and e <= b for a, b in in_decode) for s, e in pjit)
    return {
        "split": {k: split[k] / n / 1e9 for k in ("dispatch", "fence", "engine", "caller")},
        "clock": clock,
        "window_s": (hi - lo) / 1e9,
        "drained_s": drained / n / 1e9,
        "decode_pjit": (covered, len(pjit)),
        "anchors": pairs,
    }


def fill(ctx: dict, tmp: str, log) -> None:
    """Put the THAPI trace's spans, and with a TPU profile the idle split,
    under new ``ctx`` keys; ``tmp`` is the run's temporary directory."""
    from repro.core.plugins.timeline import query_intervals

    thapi = ctx["thapi_dir"] = os.path.join(tmp, "thapi")
    rows = query_intervals(thapi)
    ctx["consumer_drain_s"] = sum(d for _, d, _, _, n, _ in rows if n == DRAIN) / 1e9
    ctx["queue_wait_s"] = [d / 1e9 for _, d, _, _, n, _ in rows if n == WAIT]
    ctx["idle_split_s"] = None
    tid = serving_thread(rows)
    if tid is not None:
        log(f"[attribute] {longest_step(rows, tid)}")
    paths = glob.glob(os.path.join(ctx.get("profile_dir") or "", "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return
    devices, host = reduce.load(paths[0])
    a = attribute(devices, host, marks(paths[0]), rows)
    if a is None:
        log(f"[attribute] no idle split: {len(devices)} TPU plane(s)")
        return
    ctx["idle_split_s"] = a["split"]
    c, w = a["clock"], a["window_s"]
    offs = [p - (b0 + b1) / 2 - c.offset_ns for p, b0, b1 in a["anchors"]]
    widths = sorted(b1 - b0 for _, b0, b1 in a["anchors"])
    log(f"[attribute] clock: offset {c.offset_ns:.0f} ns, spread {c.spread_ns:.0f} ns over "
        f"{c.anchors} of {c.given} anchors (all offsets less the median: min {min(offs):.0f}, quartiles "
        f"{[round(q) for q in statistics.quantiles(offs, n=4)] if len(offs) > 1 else offs}, "
        f"max {max(offs):.0f}; brackets median {statistics.median(widths):.0f}, max {widths[-1]}); "
        f"decode PjitFunction events inside decode dispatch spans: "
        f"{a['decode_pjit'][0]} of {a['decode_pjit'][1]}")
    log("[attribute] idle split of the profiled window: "
        + ", ".join(f"{k} {100 * v / w:.3f}%" for k, v in a["split"].items())
        + f"; under a consumer drain {100 * a['drained_s'] / w:.3f}%")


def main(argv=None) -> int:
    import argparse

    from bench import run, window

    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    spec = run.load_json(ROOT / "BENCHMARK.json")
    cells = [w["name"] for w in spec["workloads"]]
    spec["per_layer"] = spec["per_layer"] + [dict(m, workloads=cells) for m in METRICS]
    reduce_trace = window.reduce_trace

    def reduce_and_fill(ctx, log):
        reduce_trace(ctx, log)
        fill(ctx, os.path.dirname(ctx["profile_dir"]), log)

    window.reduce_trace = reduce_and_fill  # run_cell imports it at call time
    try:
        result = run.run_cell(args.workload, args.seed, args.seconds, True, spec=spec)
    except SystemExit as e:
        run.log(f"[bench] {e}")
        return 2
    finally:
        window.reduce_trace = reduce_trace
    m = result["metrics"]
    parts = sum(m[f"idle_{k}.serve"]["value"] for k in ("dispatch", "fence", "engine", "caller")
                if f"idle_{k}.serve" in m)
    run.log(f"[attribute] idle parts sum to {parts} %, device_idle.serve "
            f"{m.get('device_idle.serve', {}).get('value')} %")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
