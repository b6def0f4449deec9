"""Tests of the Granite-4.0-H serving cell's harness (``bench/kinds/
serve_hybrid.py`` and its weights, reference, counts and readers), on the
CPU at a smoke size.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_hybrid.py
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import flops, flops_hybrid, run  # noqa: E402
from bench.kinds import serve_hybrid  # noqa: E402
from bench.weights_hybrid import dims, make_weights, program_config  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
GRANITE = json.loads((ROOT / "bench/configs/granite-4.0-h-small.json").read_text())
SMOKE = json.loads((ROOT / "tests/bench/smoke-hybrid-config.json").read_text())
CELL = "granite-h-small-serve-chat"
SMOKE_SPEC = {
    "configs": [{"name": "granite-h-smoke", "file": "tests/bench/smoke-hybrid-config.json"}],
    "workloads": [{"name": "smoke-hybrid", "config": "granite-h-smoke", "traffic": "smoke-hybrid", "chips": 1}],
    "end_to_end": [{"name": n, "unit": "x"} for n in ("serve_tok_s", "ttft_p95_ms", "setup_s")],
    "per_layer": [],
}


# -- the configuration as published, and its cut ----------------------------------


def test_config_is_the_published_one_cut_to_one_stage():
    catalog = {"layers": 40, "experts": 72}
    z = dims(GRANITE)
    assert GRANITE["source"].startswith("https://huggingface.co/ibm-granite/granite-4.0-h-small")
    assert (z["L"], GRANITE["reduced"]["num_hidden_layers"]["published"]) == (10, catalog["layers"])
    assert (len(z["held"]), z["E"]) == (9, catalog["experts"]) and z["held"] == tuple(range(9))
    assert (z["d"], z["H"], z["P"], z["N"], z["Hq"], z["Kv"], z["hd"]) == (4096, 128, 64, 128, 32, 8, 128)
    assert (z["k"], z["f"], z["fs"], z["V"]) == (10, 768, 1536, 100352)
    assert z["types"] == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4  # one whole period
    entry = next(c for c in SPEC["configs"] if c["name"] == GRANITE["name"])
    assert sorted(entry["reduced"]) == sorted(GRANITE["reduced"])
    assert "32 chips" in GRANITE["deployment"]


def test_program_layout_and_size():
    import jax

    from repro.models import Model

    z = dims(GRANITE)
    cfg = program_config(GRANITE, z)
    want = jax.tree_util.tree_map(lambda s: (s.shape, str(s.dtype)), Model(cfg).shapes())
    made = jax.eval_shape(lambda: make_weights(GRANITE, 2**31 + 3, "bfloat16"))
    assert jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), made) == want
    assert cfg.num_params() == 2_414_692_992  # 2.41 B: 4.83 GB in bf16


# -- flops_hybrid against hand-counted shapes -------------------------------------


def test_flash_flops_hand_counted():
    z = {"Hq": 2, "Kv": 1, "hd": 4}
    # q·kᵀ over the lower triangle of 8×8 (counted as half the square) and p·v:
    # 2 · (2·8·8·4·2 / 2) = 2·64·8
    assert flops_hybrid.flash_flops(8, z) == 2 * 8 * 8 * 2 * 4
    assert flops_hybrid.flash_bytes(8, z) == (2 * 8 * 2 * 4 + 2 * 8 * 1 * 4) * 2


def test_layer_flops_hand_counted():
    z = dict(dims(SMOKE), d=4, E=8, k=2, held=(0, 1), f=3, fs=5, Hq=2, Kv=1, hd=2)
    # router 2·4·8, 2 of 8 experts held at top-2: 0.5 pair · 6·4·3, shared 6·4·5
    assert flops_hybrid.moe_flops_per_token(z) == 64 + 36 + 120
    # q, o: 2·4·(2·2) each; k, v: 2·4·(1·2) each
    assert flops_hybrid.attn_proj_flops_per_token(z) == 2 * 4 * (2 * 4 + 2 * 2)


def test_prefill_flops_of_the_cut():
    z = dims(GRANITE)
    per = flops_hybrid.prefill_flops(1024, z) / 1024
    assert 2.5e9 < per < 2.7e9  # about as much as mamba2-1.3b's 2.48 GFLOP a token
    ssd = 9 * flops.ssd_flops(1024, 128, 64, 1, 128, 256) / 1024
    assert 0.6 < (9 * flops.block_flops_per_token(z) + ssd) / per < 0.8  # Mamba2 most of it
    d = flops_hybrid.decode_flops_per_token(z, 2000) - flops_hybrid.decode_flops_per_token(z, 1000)
    assert d == 4 * 1000 * 32 * 128  # attention over 1000 more cached tokens


# -- the entries and readers -----------------------------------------------------


def test_cell_entries_and_metrics():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("granite-4.0-h-small", "chat-hybrid", 1)
    mix = json.loads((ROOT / "bench/traffic/chat-hybrid.json").read_text())
    chat = json.loads((ROOT / "bench/traffic/chat.json").read_text())
    assert mix == dict(chat, kind="serve_hybrid")
    e2e = {m["name"] for m in run.metrics_of(SPEC, CELL, False)}
    assert e2e == {"serve_tok_s", "ttft_p95_ms", "setup_s"}
    traced = {m["name"] for m in run.metrics_of(SPEC, CELL, True)}
    assert {"expert_imbalance.serve", "flash_roofline.serve", "ssd_roofline.serve", "mfu.serve"} <= traced


def test_readers_read_a_smoke_ctx():
    from bench.peaks import PEAKS

    z = dims(GRANITE)
    ctx = {"route": {"decode": {"pairs": 900, "max_load": 200, "mean_load": 100.0, "records": 3}},
           "attn_calls": [(1024, 1), (4096, 1)], "dims": z, "peaks": PEAKS["TPU v5 lite"],
           "profile": {"ops_s": {"flash_attention.3": 0.01, "fusion.1": 1.0}}}
    assert run.reader("expert_imbalance.serve")(ctx) == pytest.approx(2.0)
    least = sum(flops_hybrid.flash_min_time(S, z, 197e12, 819e9) for S in (1024, 4096))
    assert run.reader("flash_roofline.serve")(ctx) == pytest.approx(100 * least / 0.01)
    # a run that has none of it to read (the parent's, a Mamba2 cell's) reads nothing
    empty = {"dims": z, "peaks": PEAKS["TPU v5 lite"]}
    assert run.reader("expert_imbalance.serve")(empty) is None
    assert run.reader("flash_roofline.serve")(empty) is None
    assert run.reader("flash_roofline.serve")(dict(ctx, profile={"ops_s": {"fusion.1": 1.0}})) is None


# -- a whole run on the CPU at the smoke size, and planted faults -------------------


def _smoke(seed=2**31 + 7, seconds=1.0):
    return run.run_cell("smoke-hybrid", seed, seconds, False, platform="cpu", spec=SMOKE_SPEC)


def test_smoke_run_is_correct():
    r = _smoke()
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"serve_tok_s", "ttft_p95_ms", "setup_s"}
    assert r["attempted"] > 0 and r["checks"]["profile_unreported_calls"]["value"] == 0


def _no_routed_experts(monkeypatch):
    """The routed experts' part left out: only the shared expert remains."""
    import jax.numpy as jnp

    from repro.models import moe

    orig = moe.held_moe
    monkeypatch.setattr(moe, "held_moe", lambda cfg, p, xt, grouped: orig(
        cfg, {**p, "w_down": jnp.zeros_like(p["w_down"])}, xt, grouped))


def _decode_ignores_cache(monkeypatch):
    """Decode attends only to the new token, not to the KV cache."""
    import jax.numpy as jnp

    from repro.models import mamba_hybrid

    orig = mamba_hybrid.attend

    def attend(q, k, v, *, kv_len, **kw):
        b = jnp.arange(k.shape[0])
        new = lambda c: c[b, kv_len - 1][:, None]  # noqa: E731
        return orig(q, new(k), new(v), kv_len=jnp.ones_like(kv_len), **kw)

    monkeypatch.setattr(mamba_hybrid, "attend", attend)


def _moe_branch_unscaled(monkeypatch):
    """The MoE branch added to the residual without its 0.22."""
    from repro.models import mamba_hybrid

    orig = mamba_hybrid._ffn
    monkeypatch.setattr(mamba_hybrid, "_ffn", lambda cfg, *a: orig(
        dataclasses.replace(cfg, residual_multiplier=1.0), *a))


@pytest.mark.parametrize("fault", [_no_routed_experts, _decode_ignores_cache, _moe_branch_unscaled])
def test_planted_fault_fails(monkeypatch, fault):
    fault(monkeypatch)
    r = _smoke(seed=31)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]


def test_fp8_control_fails_the_limit(tmp_path):
    cfg = SMOKE
    mix = json.loads((ROOT / "bench/traffic/smoke-hybrid.json").read_text())
    limits = json.loads((ROOT / "bench/limits/smoke-hybrid.json").read_text())
    run.compile_cache()
    ctx = serve_hybrid.run({"name": "smoke-hybrid"}, cfg, mix, 13, 1.0, False, limits, str(tmp_path),
                           0.0, lambda m: None, control=True)
    assert ctx["checks"]["logit_gap"][0] <= limits["logit_gap"] < ctx["control"]["logit_gap"]
    assert ctx["route"]["decode"]["records"] > 0 and ctx["route"]["prefill"]["records"] == ctx["requests"]
