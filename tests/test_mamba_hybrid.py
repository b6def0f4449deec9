"""The ``mamba_hybrid`` family (Granite-4.0-H) at a smoke size on the CPU:
agreement with the plain reference (``bench/reference_hybrid.py``), the
held-expert MoE's shares, continuous batching over both kinds of state, the
in-place decode, and the route counts the engine records.

Weights are seeded (``bench/weights_hybrid.py``, whose scales keep every
branch's share of a layer at the smoke widths).  Program and reference
run in float32 here, so a tolerance bounds only the order of summation:
the chunked SSD, the blocked attention, the grouped and dense MoE forms.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import reference_hybrid  # noqa: E402
from bench.weights_hybrid import dims, make_weights, program_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import Model, ShapeSpec  # noqa: E402
from repro.models import mamba_hybrid, moe  # noqa: E402
from repro.models.param import init as spec_init  # noqa: E402

SMOKE = json.loads((ROOT / "tests/bench/smoke-hybrid-config.json").read_text())
F32 = dict(SMOKE, torch_dtype="float32")
BF16 = dict(SMOKE, torch_dtype="bfloat16")
SEED = 2**31 + 21
#: f32 on both sides; the logits spread by about 0.004, and the summation
#: orders of the two sides move them by about 1e-7
TOL = dict(rtol=1e-4, atol=1e-6)


@pytest.fixture(scope="module")
def f32():
    z = dims(F32)
    return z, Model(program_config(F32, z)), make_weights(F32, SEED, "float32")


def _ref_logits(z, w, seq, positions):
    with jax.default_matmul_precision("highest"):
        S = -(-len(seq) // 64) * 64
        toks = np.zeros((S,), np.int32)
        toks[: len(seq)] = seq
        return np.asarray(reference_hybrid.logits_at(z, w, jnp.asarray(toks), jnp.asarray(positions)))


def test_smoke_config_mixes_both_kinds():
    z = dims(SMOKE)
    assert mamba_hybrid.runs(program_config(SMOKE, z)) == [
        ("mamba", 0, 0, 2), ("attention", 2, 0, 1), ("mamba", 3, 2, 2)]
    assert z["held"] == (2, 3, 4, 5) and z["E"] == 8


def test_prefill_then_decode_match_the_reference(f32):
    """Prefill of 64 tokens, then 8 donated decode steps through the cache,
    against the reference's full forward pass at each position."""
    z, model, w = f32
    rng = np.random.default_rng(5)
    seq = rng.integers(0, z["V"], size=(2, 72)).astype(np.int32)
    logits, cache, _ = model.prefill(w, {"tokens": jnp.asarray(seq[:, :64])}, 96)
    got = [np.asarray(logits[:, 0, : z["V"]])]
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    for t in range(64, 71):
        logits, cache, _ = step(w, cache, {"token": jnp.asarray(seq[:, t])})
        got.append(np.asarray(logits[:, 0, : z["V"]]))
    got = np.stack(got, axis=1)
    for b in range(2):
        want = _ref_logits(z, w, seq[b], np.arange(63, 71))
        np.testing.assert_allclose(got[b], want, **TOL)
        assert want.std() > 1e-3  # logits of a useful spread


def test_bf16_program_stays_close_to_the_reference():
    """The served precision: bf16 weights and activations against the f32
    reference of the same (rounded) weights, gap of the served argmax.  At
    these widths bf16 noise is about as large as the fp8 control's (the
    smoke cell runs f32 for that reason); 0.004 is the logits' spread."""
    z = dims(BF16)
    model = Model(program_config(BF16, z))
    w = make_weights(BF16, SEED, "bfloat16")
    seq = np.random.default_rng(6).integers(0, z["V"], size=(1, 128)).astype(np.int32)
    logits, _, _ = model.prefill(w, {"tokens": jnp.asarray(seq)}, 160)
    want = _ref_logits(z, w, seq[0], np.asarray([127]))[0]
    served = int(jnp.argmax(logits[0, 0, : z["V"]]))
    assert want.max() - want[served] < 0.004


def _layer(z, w, layer):
    return jax.tree_util.tree_map(lambda a: a[layer], w["moe"])


@pytest.mark.parametrize("grouped", [True, False])
def test_held_shares_add_up_to_the_uncut_layer(grouped):
    """Every expert held by one of three disjoint shares: the shares' parts,
    with the shared expert counted once, are the whole layer."""
    full = dict(F32, experts_held=list(range(8)))
    z = dims(full)
    w = make_weights(full, SEED, "float32")
    p = _layer(z, w, 0)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(40, z["d"])), jnp.float32)
    base = program_config(full, z)
    shares = [(0, 3, 6), (1, 4), (2, 5, 7)]
    total = 0.0
    for share in shares:
        cfg = dataclasses.replace(base, moe=dataclasses.replace(base.moe, experts_held=share, d_ff_shared=0))
        mine = {k: p[k][jnp.asarray(share)] for k in ("w_gate", "w_up", "w_down")}
        part, load = moe.held_moe(cfg, {"router": p["router"], **mine}, x, grouped)
        total = total + part
        assert int(load.sum()) <= x.shape[0] * z["k"]
    total = total + moe.swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    with jax.default_matmul_precision("highest"):
        want = reference_hybrid._moe(z, "f32", x, p)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=1e-4, atol=1e-5)
    # and the uncut layer in one call: all eight held
    whole, load = moe.held_moe(base, p, x, grouped)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), rtol=1e-4, atol=1e-5)
    assert int(load.sum()) == x.shape[0] * z["k"]


@pytest.mark.parametrize("grouped", [True, False])
def test_every_token_on_one_expert_loses_nothing(f32, grouped):
    """A router that sends every token to the same held experts: each of
    them takes all the tokens, with no capacity to drop any."""
    z, model, w = f32
    p = dict(_layer(z, w, 0))
    router = np.zeros((z["d"], z["E"]), np.float32)
    x = np.abs(np.random.default_rng(8).normal(size=(96, z["d"]))).astype(np.float32)
    router[:, 3] = 1.0  # expert 3 (held) first, expert 6 (held elsewhere) second
    router[:, 6] = 0.5
    p["router"] = jnp.asarray(router)
    out, load = moe.held_moe(model.cfg, p, jnp.asarray(x), grouped)
    assert np.asarray(load).tolist() == [0, 96, 0, 0]  # held (2, 3, 4, 5)
    with jax.default_matmul_precision("highest"):
        want = reference_hybrid._moe(z, "f32", jnp.asarray(x), p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_continuous_batching_gives_each_slot_its_own_logits(f32):
    """Prompts of different lengths admitted into one slot batch at
    different steps (the engine's splice, both kinds of state): each slot's
    logits are those of its request decoded alone."""
    from repro.serve import ServeEngine

    z, model, w = f32
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, z["V"], size=(n,)).astype(np.int32) for n in (40, 64, 16)]
    step = jax.jit(model.decode_step)
    shape = ShapeSpec("b", "decode", 96, 3)
    cache = jax.tree_util.tree_map(jnp.zeros_like, spec_init(model.cache_specs(shape), jax.random.PRNGKey(0), "float32"))
    tok = np.zeros((3,), np.int32)
    alone = {}  # slot → (its own cache row, its next token)

    def batch_step(cache, tok):
        """One decode step of the batch; every admitted slot against its own."""
        logits, cache, _ = step(w, cache, {"token": jnp.asarray(tok)})
        for slot, (row, t) in alone.items():
            one, row, _ = step(w, row, {"token": jnp.asarray([t])})
            np.testing.assert_allclose(np.asarray(logits[slot]), np.asarray(one[0]), **TOL)
            alone[slot] = (row, int(jnp.argmax(one[0, 0, : z["V"]])))
        return cache, np.asarray(jnp.argmax(logits[:, 0, : z["V"]], axis=-1)).astype(np.int32)

    for slot, p in enumerate(prompts):  # an admission, then a step, for each
        logits, row, _ = model.prefill(w, {"tokens": jnp.asarray(p[None])}, 96)
        cache = jax.tree_util.tree_map(lambda c, r: ServeEngine._splice(c, r, slot), cache, row)
        tok[slot] = int(jnp.argmax(logits[0, 0, : z["V"]]))
        alone[slot] = (row, tok[slot])
        cache, tok = batch_step(cache, tok)
    for _ in range(3):  # all three slots in flight
        cache, tok = batch_step(cache, tok)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_donated_decode_steps_match_prefill(f32, impl, monkeypatch):
    """Three donated decode steps give the logits and cache of a prefill over
    the prompt extended by the same tokens; ``pallas`` updates the Mamba2
    state in the decode kernel (interpreted on the CPU)."""
    monkeypatch.setattr(ops, "ssd_step_inplace", functools.partial(ops.ssd_step_inplace, impl=impl))
    z, model, w = f32
    seq = jnp.asarray(np.random.default_rng(10).integers(0, z["V"], size=(2, 8)), jnp.int32)
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    _, cache, _ = model.prefill(w, {"tokens": seq[:, :5]}, 16)
    for t in range(5, 8):
        logits, cache, _ = step(w, cache, {"token": seq[:, t]})
        want, want_cache, _ = model.prefill(w, {"tokens": seq[:, : t + 1]}, 16)
        np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(want[:, 0]), **TOL)
    for k in want_cache:  # the chunked prefill scan against the steps: as test_models_smoke's
        np.testing.assert_allclose(
            np.asarray(cache[k]), np.asarray(want_cache[k]), rtol=2e-4, atol=2e-4, err_msg=k
        )


@pytest.fixture(scope="module")
def donated_decode():
    """The compiled text of a donated decode step, and its cache shapes."""
    from repro.configs import get_config

    cfg = get_config("granite-4.0-h-small").smoke()
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    cache = spec_init(m.cache_specs(ShapeSpec("d", "decode", 24, 2)), jax.random.PRNGKey(1), cfg.dtype)
    step = jax.jit(m.decode_step, donate_argnums=(1,))
    text = step.lower(params, cache, {"token": jnp.zeros((2,), jnp.int32)}).compile().as_text()
    return text, {k: v.shape for k, v in cache.items()}


@pytest.mark.parametrize("leaf", ["state", "conv", "k", "v"])
def test_decode_updates_every_cache_leaf_in_place(donated_decode, leaf):
    """With the cache donated, the compiled decode step writes into each
    cache leaf: no whole-leaf copy, nor a whole-leaf broadcast."""
    text, shapes = donated_decode
    dims_ = ",".join(map(str, shapes[leaf]))
    whole = re.compile(r"= \w+\[%s\]\S* (copy|copy-start|broadcast)\(" % dims_)
    offenders = [line.strip() for line in text.splitlines() if whole.search(line)]
    assert not offenders, offenders


def test_engine_records_route_counts_with_the_tokens(tmp_path):
    """One ``moe_route`` pair per prefill and per decode step, carrying the
    counts the programs return, read back with the tokens; a model without
    experts records none."""
    from repro.configs import get_config
    from repro.core import TraceConfig, Tracer
    from repro.core.babeltrace import intervals_of
    from repro.serve import ServeConfig, ServeEngine

    z = dims(BF16)
    model = Model(program_config(BF16, z))
    w = make_weights(BF16, SEED, "bfloat16")
    eng = ServeEngine(model, w, ServeConfig(batch_slots=2, cache_len=80, max_new_tokens=4))
    rng = np.random.default_rng(10)
    prompts = [rng.integers(0, z["V"], size=(n,)) for n in (64, 16, 32)]
    with Tracer(TraceConfig(out_dir=str(tmp_path / "t"), mode="default")):
        reqs = [eng.submit(p) for p in prompts]
        steps = 0
        while eng.step():
            steps += 1
    ivs, _, _ = intervals_of(str(tmp_path / "t"))
    route = [iv for iv in ivs if iv.api == "moe_route"]
    decode = [iv for iv in route if iv.entry["decode"]]
    assert len(route) - len(decode) == len(reqs) and len(decode) == steps
    assert all(iv.entry["held"] == 4 for iv in route)
    # a decode step routes 2 slots × top-2 × 5 layers, a part of them held here
    for iv in decode:
        assert 0 < iv.exit["pairs"] <= 2 * 2 * 5
        assert iv.exit["pairs"] / 4 <= iv.exit["max_load"] <= iv.exit["pairs"]
    # the prefill's counts are those the program returns for the prompt
    _, _, want = model.prefill(w, {"tokens": jnp.asarray(prompts[0][None].astype(np.int32))}, 80)
    first = min((iv for iv in route if not iv.entry["decode"]), key=lambda iv: iv.ts)
    assert [first.exit["pairs"], first.exit["max_load"], first.entry["held"]] == np.asarray(want).tolist()
    # the readback is the only D2H copy of a decode step, as without experts
    assert sum(iv.api == "memcpy" for iv in ivs if not iv.device) == steps
    assert all(len(r.out_tokens) == 4 for r in reqs)

    plain = Model(get_config("mamba2-1.3b").smoke())
    eng = ServeEngine(plain, plain.init(jax.random.PRNGKey(0)), ServeConfig(batch_slots=2, cache_len=48, max_new_tokens=2))
    with Tracer(TraceConfig(out_dir=str(tmp_path / "m"), mode="default")):
        eng.submit(rng.integers(0, 100, size=(16,)))
        while eng.step():
            pass
    ivs, _, _ = intervals_of(str(tmp_path / "m"))
    assert not any(iv.api == "moe_route" for iv in ivs)
