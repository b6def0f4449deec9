"""Per-architecture smoke tests (assignment deliverable f).

Each assigned arch instantiates a REDUCED same-family config and runs one
forward/train step on CPU asserting output shapes + no NaNs, plus a
prefill→decode round to exercise the serving path.  The FULL configs are
exercised only via the dry-run (ShapeDtypeStructs, no allocation).
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.kernels import ops
from repro.models import Model, ShapeSpec
from repro.models.param import count as param_count, init as spec_init, shapes as spec_shapes

SMOKE_SHAPE = ShapeSpec("smoke_train", "train", 32, 2)
SMOKE_PREFILL = ShapeSpec("smoke_prefill", "prefill", 16, 2)
SMOKE_DECODE = ShapeSpec("smoke_decode", "decode", 24, 2)


def make_batch(model: Model, shape: ShapeSpec, rng):
    """Materialize a random batch matching batch_specs."""
    specs = model.batch_specs(shape)
    out = {}
    for k, s in specs.items():
        if s.dtype == "int32":
            out[k] = jnp.asarray(
                rng.integers(0, model.cfg.vocab_size, size=s.shape), jnp.int32
            )
        else:
            out[k] = jnp.asarray(rng.normal(size=s.shape) * 0.1, jnp.float32)
    return out


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(42)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_loss(arch, rng):
    cfg = get_config(arch).smoke()
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = make_batch(m, SMOKE_SHAPE, rng)
    loss, metrics = jax.jit(m.loss)(params, batch)
    assert loss.shape == ()
    assert jnp.isfinite(loss), f"{arch}: non-finite loss {loss}"
    assert float(loss) > 0
    # a plausible LM init sits near ln(V)
    assert float(metrics["ce"]) < 2 * np.log(cfg.padded_vocab)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_grad_step(arch, rng):
    cfg = get_config(arch).smoke()
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = make_batch(m, SMOKE_SHAPE, rng)

    def loss_fn(p):
        return m.loss(p, batch)[0]

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    gnorm = jnp.sqrt(
        sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree_util.tree_leaves(grads))
    )
    assert jnp.isfinite(loss) and jnp.isfinite(gnorm)
    assert float(gnorm) > 0, f"{arch}: zero gradient"


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_prefill_decode(arch, rng):
    cfg = get_config(arch).smoke()
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    batch = make_batch(m, SMOKE_PREFILL, rng)
    cache_len = SMOKE_DECODE.seq_len
    logits, cache, _ = jax.jit(lambda p, b: m.prefill(p, b, cache_len))(params, batch)
    B = SMOKE_PREFILL.global_batch
    assert logits.shape[0] == B and logits.shape[1] == 1
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    # greedy-decode 3 steps
    step = jax.jit(m.decode_step)
    tok = jnp.argmax(logits[:, 0, : cfg.vocab_size], axis=-1).astype(jnp.int32)
    for _ in range(3):
        logits, cache, _ = step(params, cache, {"token": tok})
        assert logits.shape[0] == B
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
        tok = jnp.argmax(logits[:, 0, : cfg.vocab_size], axis=-1).astype(jnp.int32)


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_consistent(arch):
    """Spec tree, shapes tree and logical axes tree stay in lockstep."""
    cfg = get_config(arch)
    m = Model(cfg)
    shapes = m.shapes()
    axes = m.axes()
    flat_s = jax.tree_util.tree_leaves(shapes)
    flat_a = jax.tree_util.tree_leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    assert len(flat_s) == len(flat_a)
    for sds, ax in zip(flat_s, flat_a):
        assert len(sds.shape) == len(ax)
    # analytic count vs spec-tree count within 2% (analytic skips tiny terms)
    spec_total = param_count(m.param_specs())
    analytic = cfg.num_params()
    assert abs(spec_total - analytic) / analytic < 0.02, (arch, spec_total, analytic)


def test_decode_matches_prefill_continuation(rng):
    """Decoding token-by-token must equal teacher-forced prefill logits."""
    cfg = get_config("h2o-danube-1.8b").smoke()
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(2))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 12)), jnp.int32)
    cache_len = 32
    # full prefill over 12 tokens
    full_logits, _, _ = m.prefill(params, {"tokens": toks}, cache_len)
    # prefill over 11 then decode the 12th
    _, cache, _ = m.prefill(params, {"tokens": toks[:, :-1]}, cache_len)
    step_logits, _, _ = m.decode_step(params, cache, {"token": toks[:, -1]})
    np.testing.assert_allclose(
        np.asarray(full_logits[:, 0]), np.asarray(step_logits[:, 0]), rtol=2e-4, atol=2e-4
    )


def test_ssm_decode_matches_prefill_continuation(rng):
    cfg = get_config("mamba2-1.3b").smoke()
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(3))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 8)), jnp.int32)
    full_logits, _, _ = m.prefill(params, {"tokens": toks}, 16)
    _, cache, _ = m.prefill(params, {"tokens": toks[:, :-1]}, 16)
    step_logits, _, _ = m.decode_step(params, cache, {"token": toks[:, -1]})
    np.testing.assert_allclose(
        np.asarray(full_logits[:, 0]), np.asarray(step_logits[:, 0]), rtol=2e-4, atol=2e-4
    )


def test_ssm_donated_decode_steps_match_prefill(rng):
    """Three donated decode steps give the logits and cache of a prefill over
    the prompt extended by the same tokens."""
    _donated_decode_steps_match_prefill(rng)


def test_ssm_donated_decode_steps_through_the_kernel_match_prefill(rng, monkeypatch):
    """The same with the decode step's state update in the Pallas kernel
    (interpreted on the CPU), prefill on the reference."""
    monkeypatch.setattr(ops, "ssd_step_inplace", functools.partial(ops.ssd_step_inplace, impl="pallas"))
    _donated_decode_steps_match_prefill(rng)


def _donated_decode_steps_match_prefill(rng):
    cfg = get_config("mamba2-1.3b").smoke()
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(3))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 8)), jnp.int32)
    step = jax.jit(m.decode_step, donate_argnums=(1,))
    _, cache, _ = m.prefill(params, {"tokens": toks[:, :5]}, 16)
    for t in range(5, 8):
        step_logits, cache, _ = step(params, cache, {"token": toks[:, t]})
        full_logits, full_cache, _ = m.prefill(params, {"tokens": toks[:, : t + 1]}, 16)
        np.testing.assert_allclose(
            np.asarray(full_logits[:, 0]), np.asarray(step_logits[:, 0]), rtol=2e-4, atol=2e-4
        )
    for k in ("conv", "state", "len"):
        np.testing.assert_allclose(
            np.asarray(full_cache[k]), np.asarray(cache[k]), rtol=2e-4, atol=2e-4, err_msg=k
        )
    # at this init the state is about 1e-5 across: the same 2e-4, of its scale
    want = np.asarray(full_cache["state"])
    np.testing.assert_allclose(np.asarray(cache["state"]), want, rtol=2e-4, atol=2e-4 * np.abs(want).max())


@pytest.mark.parametrize("leaf", ["state", "conv"])
def test_ssm_decode_updates_cache_in_place(leaf):
    """With the cache donated, the compiled decode step writes each layer's
    slice into the cache buffer: no whole-leaf copy, nor a whole-leaf
    broadcast initialising a fresh buffer."""
    cfg = get_config("mamba2-1.3b").smoke()
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    cache = spec_init(m.cache_specs(SMOKE_DECODE), jax.random.PRNGKey(1), cfg.dtype)
    token = {"token": jnp.zeros((SMOKE_DECODE.global_batch,), jnp.int32)}
    step = jax.jit(m.decode_step, donate_argnums=(1,))
    text = step.lower(params, cache, token).compile().as_text()
    dims = ",".join(map(str, cache[leaf].shape))
    whole = re.compile(r"= \w+\[%s\]\S* (copy|copy-start|broadcast)\(" % dims)
    offenders = [line.strip() for line in text.splitlines() if whole.search(line)]
    assert not offenders, offenders


def test_hybrid_decode_matches_prefill_continuation(rng):
    cfg = get_config("recurrentgemma-2b").smoke()
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(4))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 8)), jnp.int32)
    full_logits, _, _ = m.prefill(params, {"tokens": toks}, 16)
    _, cache, _ = m.prefill(params, {"tokens": toks[:, :-1]}, 16)
    step_logits, _, _ = m.decode_step(params, cache, {"token": toks[:, -1]})
    np.testing.assert_allclose(
        np.asarray(full_logits[:, 0]), np.asarray(step_logits[:, 0]), rtol=2e-4, atol=2e-4
    )


def test_moe_dense_oracle_consistency(rng):
    """Single-device MoE path: top-k combine weights sum to 1, loss finite."""
    from repro.models.moe import _moe_dense, _router

    cfg = get_config("moonshot-v1-16b-a3b").smoke()
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(5))
    xt = jnp.asarray(rng.normal(size=(6, cfg.d_model)), jnp.float32)
    p_layer = jax.tree_util.tree_map(lambda x: x[0], params["blocks"])
    w, idx, aux = _router(cfg, p_layer["router"], xt)
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 1.0, rtol=1e-5)
    assert float(aux) >= 1.0 - 1e-5  # aux loss lower bound at uniform routing
    out, _ = _moe_dense(
        cfg, {k: p_layer[k] for k in ("router", "w_gate", "w_up", "w_down")}, xt
    )
    assert out.shape == xt.shape and bool(jnp.all(jnp.isfinite(out)))
