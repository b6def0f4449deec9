"""Substrate tests: data pipeline, optimizer, compression, checkpointing,
sharding rules, schedules."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests.hypothesis_optional import given, settings, st
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.jaxcompat import make_abstract_mesh, make_mesh
from repro.data import DataConfig, SyntheticPipeline
from repro.models import Model, ShapeSpec
from repro.optim import (
    AdamWConfig,
    adamw_init,
    adamw_update,
    dequantize_int8,
    global_norm,
    quantize_int8,
    topk_sparsify,
    warmup_cosine,
)
from repro.optim.compression import topk_densify
from repro.sharding import Partitioner, logical_to_pspec


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def mk_pipe(**kw):
    m = Model(get_config("h2o-danube-1.8b").smoke())
    return SyntheticPipeline(m, ShapeSpec("t", "train", 16, 4), **kw)


def test_pipeline_deterministic_per_step():
    a, b = mk_pipe(), mk_pipe()
    for _ in range(3):
        ba, bb = next(a), next(b)
        np.testing.assert_array_equal(ba["tokens"], bb["tokens"])


def test_pipeline_rank_disjoint():
    a = mk_pipe(dp_rank=0, dp_size=2)
    b = mk_pipe(dp_rank=1, dp_size=2)
    assert not np.array_equal(next(a)["tokens"], next(b)["tokens"])
    assert a.local_batch == 2


def test_pipeline_state_restore_resumes_exactly():
    p = mk_pipe()
    next(p)
    next(p)
    state = p.state_dict()
    want = next(p)
    q = mk_pipe()
    q.load_state_dict(state)
    got = next(q)
    np.testing.assert_array_equal(want["tokens"], got["tokens"])


def test_pipeline_prefetch_matches_sync():
    sync = mk_pipe()
    pre = mk_pipe().start()
    try:
        for _ in range(4):
            np.testing.assert_array_equal(next(sync)["tokens"], next(pre)["tokens"])
    finally:
        pre.stop()


def test_pipeline_labels_are_shifted_tokens():
    b = next(mk_pipe())
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])


def test_pipeline_frontend_stubs():
    m = Model(get_config("whisper-medium").smoke())
    p = SyntheticPipeline(m, ShapeSpec("t", "train", 16, 2))
    b = next(p)
    assert "frames" in b and b["frames"].shape[0] == 2
    mv = Model(get_config("llava-next-34b").smoke())
    pv = SyntheticPipeline(mv, ShapeSpec("t", "train", 16, 2))
    bv = next(pv)
    assert "patch_embeds" in bv
    assert bv["tokens"].shape[1] == 16 - mv.cfg.vision_tokens


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_decreases_quadratic():
    cfg = AdamWConfig(weight_decay=0.0, clip_norm=1e9)
    params = {"w": jnp.array([3.0, -2.0])}
    state = adamw_init(params, cfg)

    def loss(p):
        return jnp.sum(p["w"] ** 2)

    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state, _ = adamw_update(g, state, params, 0.05, cfg)
    assert float(loss(params)) < 1e-2


def test_adamw_clips_gradients():
    cfg = AdamWConfig(clip_norm=1.0)
    params = {"w": jnp.zeros(4)}
    state = adamw_init(params, cfg)
    g = {"w": jnp.full((4,), 100.0)}
    _, _, gnorm = adamw_update(g, state, params, 0.1, cfg)
    assert float(gnorm) == pytest.approx(200.0)  # reported pre-clip


def test_adamw_bf16_state_dtype():
    cfg = AdamWConfig(state_dtype="bfloat16")
    params = {"w": jnp.zeros((8,), jnp.bfloat16)}
    state = adamw_init(params, cfg)
    assert state["mu"]["w"].dtype == jnp.bfloat16


def test_warmup_cosine_shape():
    lrs = [float(warmup_cosine(s, peak_lr=1.0, warmup=10, total=100)) for s in range(100)]
    assert lrs[0] == 0.0
    assert max(lrs) == pytest.approx(1.0, abs=1e-3)
    assert np.argmax(lrs) == 10
    assert lrs[-1] < 0.2


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=1, max_value=64), st.floats(min_value=0.01, max_value=100))
def test_property_int8_quantization_error_bound(n, scale):
    x = jnp.asarray(np.random.default_rng(n).normal(size=(4, n)) * scale, jnp.float32)
    q, s = quantize_int8(x)
    back = dequantize_int8(q, s)
    maxerr = float(jnp.max(jnp.abs(back.reshape(x.shape) - x)))
    bound = float(jnp.max(s)) * 0.5 + 1e-6  # half an int8 step per row
    assert maxerr <= bound


def test_quantize_zero_tensor():
    q, s = quantize_int8(jnp.zeros((3, 5)))
    assert float(jnp.max(jnp.abs(dequantize_int8(q, s)))) == 0.0


def test_topk_roundtrip():
    x = jnp.asarray([0.1, -5.0, 0.2, 3.0, -0.05])
    vals, idx = topk_sparsify(x, 2)
    dense = topk_densify(vals, idx, 5)
    np.testing.assert_allclose(np.asarray(dense), [0, -5.0, 0, 3.0, 0])


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh11():
    return make_mesh((1, 1), ("data", "model"))


def test_pspec_divisible(mesh11):
    mesh = make_mesh((1, 1), ("data", "model"))
    # with axis size 1, everything falls back to replication
    assert logical_to_pspec(("vocab", "embed"), (32000, 128), mesh) == P()


def test_pspec_nondivisible_falls_back():
    # simulate a 16-way model axis via an abstract mesh
    mesh = make_abstract_mesh((16,), ("model",))
    assert logical_to_pspec(("heads", None, None), (40, 1, 1), mesh) == P()  # 40 % 16 ≠ 0
    assert logical_to_pspec(("heads", None, None), (64, 1, 1), mesh) == P("model")


def test_pspec_batch_axes_multi_pod():
    mesh = make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    assert logical_to_pspec(("batch", "seq"), (256, 4096), mesh) == P(("pod", "data"))
    # batch=1 cannot shard
    assert logical_to_pspec(("batch",), (1,), mesh) == P()


def test_pspec_no_axis_reuse():
    mesh = make_abstract_mesh((16, 16), ("data", "model"))
    # both dims want "model": only the first gets it
    spec = logical_to_pspec(("mlp", "channels"), (1600, 1600), mesh)
    assert spec == P("model")


def test_fsdp_rules_shard_embed_over_data():
    mesh = make_abstract_mesh((16, 16), ("data", "model"))
    part = Partitioner(mesh, fsdp=True)
    spec = part.pspec(("embed", "mlp"), (4096, 1600))
    assert spec == P("data", "model")


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself; the helper sets
    nothing."""
    from repro.jaxcompat import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_checkout_path(monkeypatch):
    """Unset: a fixed path in the checkout, the same on every call."""
    from repro.jaxcompat import CHECKOUT, enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(CHECKOUT / ".jax_cache") == enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert (CHECKOUT / "src" / "repro" / "jaxcompat.py").is_file()
