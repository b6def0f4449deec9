"""THAPI spans of the serve engine and of the tracer's consumer; stable
program names; the trace clock placed on a profile."""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import TraceConfig, Tracer
from repro.core.babeltrace import intervals_of
from repro.core.clock import ProfileClock, profile_clock
from repro.core.interception import nbytes_of
from repro.core.plugins.tally import tally_trace
from repro.jaxcompat import make_mesh
from repro.models import Model
from repro.serve import ServeConfig, ServeEngine


@pytest.fixture(scope="module")
def engine():
    model = Model(get_config("mamba2-1.3b").smoke(), make_mesh((1, 1), ("data", "model")))
    params = model.init(jax.random.PRNGKey(0))
    return ServeEngine(model, params, ServeConfig(batch_slots=2, cache_len=48, max_new_tokens=3))


@pytest.fixture(scope="module")
def served(engine, tmp_path_factory):
    """Five requests of two lengths drained under a default-mode session."""
    d = str(tmp_path_factory.mktemp("serve") / "t")
    rng = np.random.default_rng(3)
    with Tracer(TraceConfig(out_dir=d, mode="default")):
        reqs = [engine.submit(rng.integers(0, 100, size=(n,))) for n in (16, 32, 16, 32, 16)]
        steps = 0
        while engine.step():
            steps += 1
        time.sleep(0.15)  # consumer ticks with something to drain
    ivs, _, _ = intervals_of(d)
    return d, reqs, steps, ivs


def _named(ivs, api):
    return [iv for iv in ivs if iv.api == api and not iv.device]


def test_engine_step_encloses_prefill_and_decode(served):
    _, reqs, steps, ivs = served
    outer = _named(ivs, "engine_step")
    assert len(outer) == steps + 1  # the last step finds every slot empty
    assert [iv.entry["step"] for iv in outer] == list(range(steps + 1))
    for api in ("prefill", "decode_step"):
        for iv in _named(ivs, api):
            assert any(o.tid == iv.tid and o.ts <= iv.ts and iv.ts + iv.dur <= o.ts + o.dur for o in outer)
    assert sum(iv.exit["admitted"] for iv in outer) == len(reqs)
    assert sum(iv.exit["tokens_out"] for iv in outer) == sum(len(r.out_tokens) for r in reqs)
    assert outer[0].entry["active"] == 0


def test_one_queue_wait_per_admitted_request(served):
    _, reqs, _, ivs = served
    waits = _named(ivs, "queue_wait")
    assert sorted(iv.entry["request_id"] for iv in waits) == sorted(r.rid for r in reqs)
    by_rid = {iv.entry["request_id"]: iv for iv in waits}
    prefills = {iv.entry["request_id"]: iv for iv in _named(ivs, "prefill")}
    for r in reqs:
        assert by_rid[r.rid].ts == r.t_submit
        assert by_rid[r.rid].ts + by_rid[r.rid].dur <= prefills[r.rid].ts


def test_consumer_drain_runs_on_its_own_thread(served):
    _, _, _, ivs = served
    drains = _named(ivs, "consumer_drain")
    engine_tids = {iv.tid for iv in _named(ivs, "engine_step")}
    assert drains and len(engine_tids) == 1
    assert all(iv.tid not in engine_tids for iv in drains)
    assert all(iv.exit["records"] > 0 and iv.exit["bytes"] > 0 for iv in drains)


def test_serving_calls_are_counted_as_before(served):
    d, reqs, steps, _ = served
    t = tally_trace(d)
    calls = {api: st.calls for (_, api), st in t.apis.items()}
    assert calls["prefill"] == len(reqs)
    assert calls["decode_step"] == steps
    assert calls["dispatch"] == len(reqs) + steps
    assert calls["memcpy"] == steps  # the token readback of each decode step


def test_each_admission_is_one_dispatch_donating_the_cache(served, engine):
    """N admissions and M decode steps are N prefill and N + M dispatch
    calls, the count the benchmark checks; each prefill program's dispatch
    donates the whole batch cache, into which it writes the prompt's row."""
    _, reqs, steps, ivs = served
    dispatches = _named(ivs, "dispatch")
    prefills = [iv for iv in dispatches if iv.entry["fn"].startswith("prefill[")]
    assert len(_named(ivs, "prefill")) == len(prefills) == len(reqs)
    assert len(dispatches) == len(reqs) + steps
    cache_bytes = nbytes_of(engine.cache)
    assert cache_bytes > 0
    assert [iv.entry["donated_bytes"] for iv in prefills] == [cache_bytes] * len(reqs)


def test_idle_session_records_nothing(tmp_path):
    with Tracer(TraceConfig(out_dir=str(tmp_path / "t"), mode="default")) as tr:
        time.sleep(0.12)  # two consumer ticks with nothing to drain
    assert tr.handle.events == 0


def test_consumer_ticks_carry_the_clock_anchor(tmp_path):
    d = str(tmp_path / "t")
    with Tracer(TraceConfig(out_dir=d, mode="default")) as tr:
        rec = tr.tp.record["ust_repro:data_next_entry"]
        for i in range(4):
            rec(i)
            time.sleep(0.06)
    drains = _named(intervals_of(d)[0], "consumer_drain")
    assert drains
    for iv in drains:
        assert iv.entry["clock_anchor"] <= iv.ts  # stamped before the mark, the entry after
        assert iv.tid != threading.get_ident()


def test_programs_take_the_traced_names(engine):
    batch = {"token": engine._tok}
    text = engine._decode.lower(engine.params, engine.cache, batch).as_text()
    assert "module @jit_decode_step_mamba2_1_3b_smoke " in text
    engine.submit(np.arange(16) % 100)
    engine.run_until_drained()
    text = (
        engine._prefill_jits[16]
        .lower(engine.params, {"tokens": np.zeros((1, 16), np.int32)}, engine.cache, engine._tok, np.int32(0))
        .as_text()
    )
    assert "module @jit_prefill_mamba2_1_3b_smoke_S16 " in text


def test_profile_clock_is_the_median_offset_with_its_spread():
    assert profile_clock([]) is None
    c = profile_clock([(110, 0, 20), (212, 100, 120), (305, 200, 220)])
    assert c == ProfileClock(offset_ns=100, spread_ns=7, anchors=3, given=3)
    # a bracket over twice the median one was held up: it is left out
    held = profile_clock([(110, 0, 20), (212, 100, 120), (305, 200, 220), (600, 300, 360)])
    assert held == ProfileClock(offset_ns=100, spread_ns=7, anchors=3, given=4)
