"""Tests of ``bench/attribute.py`` and its readers, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_attribute.py
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import attribute, reduce, run  # noqa: E402
from bench.kinds import serve  # noqa: E402

IDLE = ["idle_dispatch.serve", "idle_fence.serve", "idle_engine.serve", "idle_caller.serve"]


def _row(name, ts, end, tid=1):
    return (ts, end - ts, 7, tid, name, False)


# -- the split on a synthetic trace ------------------------------------------------


def test_split_recovers_the_offset_and_partitions_the_idle_time():
    off = -5_000_000  # profile ns = trace ns + off
    t0 = 10_000_000  # trace time of the window's start
    us = 1_000
    # THAPI, on the serving thread (tid 1): a step with a dispatch whose
    # fence runs from 40 to 80 µs, then a step of nothing but bookkeeping
    rows = [_row(attribute.STEP, t0, t0 + 100 * us), _row(attribute.DISPATCH, t0 + 20 * us, t0 + 80 * us),
            _row(attribute.FENCE, t0 + 40 * us, t0 + 80 * us), _row(attribute.DECODE, t0 + 15 * us, t0 + 85 * us),
            _row(attribute.STEP, t0 + 150 * us, t0 + 200 * us)]
    # consumer ticks (tid 2) at 50 ms: each mark sits 1 µs into its 2 µs
    # bracket, but for one tick held up for 300 µs after its mark
    marks = []
    for i, (jitter, after) in enumerate([(0, 2), (0, 2), (600, 2), (0, 2), (0, 2), (0, 300)]):
        before = t0 - 200 * us + i * 50_000 * us
        rows.append(_row(attribute.DRAIN, before + after * us, before + 330 * us, tid=2))
        marks.append((before + us + jitter + off, before))
    p = lambda t: t0 + t * us + off  # noqa: E731  (µs into the window → profile ns)
    devices = {"/device:TPU:0": [("fusion.1", p(-50), p(10)), ("fusion.2", p(30), p(45)),
                                 ("copy.3", p(60), p(70)), ("fusion.4", p(110), p(160))]}
    host = [(attribute.WINDOW, p(0), p(200)), ("PjitFunction(decode_step_m)", p(21), p(30)),
            ("PjitFunction(decode_step_m)", p(90), p(95))]  # the second lies in no dispatch
    a = attribute.attribute(devices, host, marks, rows)
    assert a["clock"].offset_ns == off
    assert (a["clock"].anchors, a["clock"].given, a["clock"].spread_ns) == (5, 6, 600)
    # idle 10-30 (engine 10, dispatch 10), 45-60 (fence), 70-110 (fence 10,
    # engine 20, caller 10), 160-200 (engine): 115 µs of a 200 µs window
    assert a["split"] == pytest.approx({"dispatch": 10e-6, "fence": 25e-6, "engine": 70e-6, "caller": 10e-6})
    busy_s = reduce.reduce_profile("t", window=attribute.WINDOW, loaded=(devices, host))["busy_s"]
    assert sum(a["split"].values()) == pytest.approx(a["window_s"] - busy_s)
    assert a["decode_pjit"] == (1, 2)
    # the first tick's drain runs to 130 µs: idle 10-30, 45-60 and 70-110 lie under it
    assert a["drained_s"] == pytest.approx(75e-6)


def test_a_mark_whose_tick_left_no_drain_is_not_an_anchor():
    rows = [_row(attribute.DRAIN, 1_000, 5_000, tid=2)]
    assert attribute.anchors([(50.0, 900), (60.0, 2_000_000)], [1_000]) == [(50.0, 900, 1_000)]
    assert attribute.attribute({"/device:TPU:0": []}, [], [], rows) is None  # no anchor


# -- the clock on the CPU profiler ----------------------------------------------------


def test_thapi_dispatch_covers_the_profilers_jit_call(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.core import TraceConfig, Tracer, traced_jit
    from repro.core.clock import profile_clock
    from repro.core.plugins.timeline import query_intervals

    f = traced_jit(lambda a: (a @ a).sum(), name="double_sum")
    x = jnp.ones((64, 64))
    f.jit(x).block_until_ready()
    with Tracer(TraceConfig(out_dir=str(tmp_path / "thapi"), mode="default")):
        jax.profiler.start_trace(str(tmp_path / "profile"))
        for _ in range(5):  # a call per consumer tick, so each tick drains and anchors
            f(x)
            time.sleep(0.06)
        jax.profiler.stop_trace()
    path = str(next((tmp_path / "profile").rglob("*.xplane.pb")))
    rows = query_intervals(str(tmp_path / "thapi"))
    anchors = attribute.anchors(attribute.marks(path), [s for s, _ in attribute._spans(rows, attribute.DRAIN)])
    assert len(anchors) >= 2
    clock = profile_clock(anchors)
    # the mid-stamp stands for the mark's start to within half its bracket
    slack = max(b - a for _, a, b in anchors) / 2
    _, host = reduce.load(path)
    calls = [(s, e) for n, s, e in host if n == "PjitFunction(double_sum)"]
    spans = attribute._spans(rows, attribute.DISPATCH, shift=clock.offset_ns)
    assert len(spans) == 5 and calls
    for s, e in calls:  # nested PjitFunction events of one call share its span
        assert any(a - slack <= s and e <= b for a, b in spans), (s, e, spans, clock, slack)


# -- the readers on a smoke run -----------------------------------------------------------


def test_readers_on_the_ctx_of_a_smoke_run(tmp_path):
    cfg = json.loads((ROOT / "tests/bench/smoke-config.json").read_text())
    mix = json.loads((ROOT / "bench/traffic/smoke.json").read_text())
    limits = json.loads((ROOT / "bench/limits/smoke.json").read_text())
    run.compile_cache()
    lines = []
    ctx = serve.run({"name": "smoke"}, cfg, mix, 2**31 + 21, 1.5, True, limits, str(tmp_path),
                    0.0, lines.append)
    attribute.fill(ctx, str(tmp_path), lines.append)
    assert ctx["thapi_dir"] == str(tmp_path / "thapi")
    busy = run.reader("consumer_busy.serve")(ctx)
    assert 0 < busy < 100
    assert len(ctx["queue_wait_s"]) >= ctx["requests"]
    assert run.reader("queue_wait_p95_ms.serve")(ctx) > 0
    for name in IDLE:  # the CPU profile has no TPU plane
        assert run.reader(name)(ctx) is None
    assert any("longest engine_step" in m for m in lines)


def test_metrics_are_named_like_their_readers():
    for m in attribute.METRICS:
        assert callable(run.reader(m["name"]))
        assert m["moves"] in {e["name"] for e in run.load_json(ROOT / "BENCHMARK.json")["end_to_end"]}
