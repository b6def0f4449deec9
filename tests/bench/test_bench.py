"""Tests of the benchmark harness, on the CPU at a smoke size.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench

They load no TPU library: the harness's look for a chip is told to accept
the CPU (``platform="cpu"``) only where a test drives a whole run.
"""

from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import flops, reduce, run  # noqa: E402
from bench.kinds import serve  # noqa: E402
from bench.weights import dims  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
MAMBA = json.loads((ROOT / "bench/configs/mamba2-1.3b.json").read_text())
SMOKE_SPEC = {
    "configs": [{"name": "mamba2-smoke", "file": "tests/bench/smoke-config.json"}],
    "workloads": [{"name": "smoke", "config": "mamba2-smoke", "traffic": "smoke", "chips": 1}],
    "end_to_end": [{"name": n, "unit": "x"} for n in ("serve_tok_s", "ttft_p95_ms", "setup_s")],
    "per_layer": [],
}


# -- flops.py against hand-counted shapes ------------------------------------


def test_ssd_flops_hand_counted():
    # one chunk of 4, one head of 2, state 3, one group:
    # 2·16·2 (C·Bᵀ∘L · x) + 4·4·3·2 (state read-out + update) + 2·16·3 (C·Bᵀ)
    assert flops.ssd_flops(S=4, H=1, P=2, G=1, N=3, Q=4) == 64 + 96 + 96
    # chunks and heads multiply; C·Bᵀ is shared by the heads of a group
    assert flops.ssd_flops(S=8, H=2, P=2, G=1, N=3, Q=4) == 2 * (2 * (64 + 96) + 96)


def test_ssd_mamba2_sits_near_the_v5e_ridge():
    z = dims(MAMBA)
    f = flops.ssd_flops(2048, z["H"], z["P"], z["G"], z["N"], z["chunk"])
    b = flops.ssd_bytes(2048, z["H"], z["P"], z["G"], z["N"])
    assert 150 < f / b < 300  # the v5e ridge is 197e12 / 819e9 ≈ 240 FLOP/byte


def test_model_flops_match_the_parameter_count():
    z = dims(MAMBA)
    per_layer = z["d"] * (2 * z["di"] + 2 * z["G"] * z["N"] + z["H"]) + z["di"] * z["d"]
    dense = flops.block_flops_per_token(z) - 2 * z["K"] * z["conv"]
    assert dense == 2 * per_layer
    assert flops.head_flops(z) == 2 * 2048 * 50277


def test_weights_take_the_program_layout():
    # the published head is tied to the embedding: no separate head leaf
    import jax

    from bench.weights import make_weights
    from bench.window import program_config
    from repro.models import Model

    model = Model(program_config(MAMBA, dims(MAMBA)))
    want = jax.tree_util.tree_map(lambda s: (s.shape, str(s.dtype)), model.shapes())
    made = jax.eval_shape(lambda: make_weights(MAMBA, 2**31 + 3, MAMBA["torch_dtype"]))
    assert jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), made) == want
    assert "head" not in made["embed"] and made["embed"]["tok"].shape == (50288, 2048)


# -- traffic ---------------------------------------------------------------


@pytest.mark.parametrize("mix", ["chat", "chat-full", "code", "smoke"])
def test_traffic_is_bucketed_and_fixed_per_seed(mix):
    m = json.loads((ROOT / f"bench/traffic/{mix}.json").read_text())
    a = serve.request_pool(m, 1000, 2**31 + 5)
    b = serve.request_pool(m, 1000, 2**31 + 5)
    c = serve.request_pool(m, 1000, 3)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    assert {len(p) for p in a} <= set(m["prompt_buckets"])
    # every block holds the same lengths, whatever the seed
    k = m["block"]
    for pool in (a, c):
        for i in range(0, len(pool), k):
            assert sorted(len(p) for p in pool[i : i + k]) == sorted(serve.stratified_lengths(m))


@pytest.mark.parametrize("mix, median, top, share", [("chat", 1024, 4096, 0.082), ("code", 2048, 8192, 0.102)])
def test_lengths_follow_the_lognormal(mix, median, top, share):
    m = json.loads((ROOT / f"bench/traffic/{mix}.json").read_text())
    lens = serve.stratified_lengths(m)
    assert statistics.median_low(lens) == median
    # the top bucket holds P(len > the one below it) of the log-normal
    assert abs(lens.count(top) / len(lens) - share) < 1.0 / m["block"]


@pytest.mark.parametrize("mix", ["chat", "chat-full", "code"])
def test_cell_mixes_name_their_source(mix):
    m = json.loads((ROOT / f"bench/traffic/{mix}.json").read_text())
    assert "arXiv" in m["source"] and str(m["prompt_lognormal"]["median"]) in m["source"]
    assert str(m["output_tokens"]) in m["source"]


# -- BENCHMARK.json resolves by name --------------------------------------------


def test_every_entry_resolves():
    cells = {w["name"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["source"] == c["source"]
    for w in SPEC["workloads"]:
        assert any(c["name"] == w["config"] for c in SPEC["configs"])
        assert (ROOT / f"bench/traffic/{w['traffic']}.json").is_file()
        assert (ROOT / f"bench/limits/{w['name']}.json").is_file()
        e2e = run.metrics_of(SPEC, w["name"], False)
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert run.metrics_of(SPEC, w["name"], True)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(run.reader(m["name"]))
        assert set(m.get("workloads", cells)) <= cells
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e


# -- reduce.py -----------------------------------------------------------------


def _ev(name, s, e):
    return (name, float(s), float(e))


def test_reduce_busy_idle_and_gaps():
    devices = {"/device:TPU:0": [_ev("fusion.1", 10, 30), _ev("ssd_scan.2", 25, 40),
                                 _ev("while", 70, 90), _ev("fusion.1", 72, 80),
                                 _ev("fusion.3", 80, 86), _ev("outside", 200, 300)]}
    host = [_ev("bench.window", 0, 100), _ev("engine.step", 0, 100), _ev("submit", 45, 65)]
    p = reduce.reduce_profile("t", window="bench.window", loaded=(devices, host))
    assert p["busy_s"] == pytest.approx(50e-9)  # [10, 40] ∪ [70, 90]
    assert p["window_s"] == pytest.approx(100e-9)
    assert p["ops_s"]["fusion.1"] == pytest.approx(28e-9)
    assert p["ops_s"]["while"] == pytest.approx(6e-9)  # less the 14 of its body's ops
    assert reduce.kernel_seconds(p, "ssd_scan") == pytest.approx(15e-9)
    assert reduce.kernel_seconds(p, "ssd") is None  # the whole kernel name, not a prefix
    assert [g[0] for g in p["idle_gaps"]] == ["submit", "engine.step", "engine.step"]
    assert [g[1] for g in p["idle_gaps"]] == pytest.approx([30e-9, 10e-9, 10e-9])


def test_short_op_names():
    op = "%ssd_scan.1 = (bf16[1,64,512,64]{3,2,1,0}) custom-call(bf16[1,64,512,64] %bitcast.10)"
    assert reduce.short_name(op) == "ssd_scan.1"
    assert reduce.short_name("%fusion = bf16[] fusion(bf16[2048] %ssd_scan.1)") == "fusion"


def test_reduce_reads_a_cpu_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    devices, host = reduce.load(str(path))
    assert any(n == "bench.window" for n, _, _ in host)
    with pytest.raises(ValueError, match="plane"):  # the CPU has no TPU plane
        reduce.reduce_profile(str(path), window="bench.window", loaded=(devices, host))


# -- a whole run on the CPU at the smoke size ---------------------------------------


def test_run_refuses_without_a_tpu(capsys):
    rc = run.main(["--workload", SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""


def _smoke(seed=2**31 + 7, seconds=1.5):
    return run.run_cell("smoke", seed, seconds, False, platform="cpu", spec=SMOKE_SPEC)


def test_smoke_run_is_correct():
    r = _smoke()
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"serve_tok_s", "ttft_p95_ms", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"


# -- faults in the timed path must come out as not correct ---------------------------


def _shift_tokens(orig):
    """Every decoded token moved to the next id where the logits are made."""
    import jax.numpy as jnp

    def decode_step(cfg, params, cache, batch):
        logits, new = orig(cfg, params, cache, batch)
        return jnp.roll(logits, 1, axis=-1), new

    return decode_step


def _freeze_state(orig):
    """The decode step hands back the state it was given."""

    def decode_step(cfg, params, cache, batch):
        logits, new = orig(cfg, params, cache, batch)
        return logits, {**cache, "len": new["len"]}

    return decode_step


@pytest.mark.parametrize("fault", [_shift_tokens, _freeze_state])
def test_fault_in_the_decode_step_fails(monkeypatch, fault):
    from repro.models import ssm

    monkeypatch.setattr(ssm, "decode_step", fault(ssm.decode_step))
    r = _smoke(seed=11)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]


def test_tracer_that_drops_calls_unreported_fails(monkeypatch):
    import contextlib

    from repro.serve import engine

    real, n = engine.decode_step_span, [0]

    def every_other(*a):
        n[0] += 1
        return real(*a) if n[0] % 2 else contextlib.nullcontext(_Outs())

    monkeypatch.setattr(engine, "decode_step_span", every_other)
    r = _smoke(seed=12)
    assert not r["correct"]
    assert r["checks"]["profile_unreported_calls"]["value"] > 0


class _Outs:
    outs: dict = {}


def test_fp8_control_fails_the_limit(tmp_path):
    cfg = json.loads((ROOT / "tests/bench/smoke-config.json").read_text())
    mix = json.loads((ROOT / "bench/traffic/smoke.json").read_text())
    limits = json.loads((ROOT / "bench/limits/smoke.json").read_text())
    run.compile_cache()
    ctx = serve.run({"name": "smoke"}, cfg, mix, 13, 1.5, False, limits, str(tmp_path),
                    0.0, lambda m: None, control=True)
    assert ctx["checks"]["logit_gap"][0] <= limits["logit_gap"] < ctx["control"]["logit_gap"]

