"""Pallas kernel validation: shape/dtype sweeps + hypothesis properties,
always against the pure-jnp oracles in kernels/ref.py (interpret=True on CPU
— the kernel body itself executes)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests.hypothesis_optional import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.ssd_scan import ssd_pallas

RNG = np.random.default_rng(0)


def randn(*shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,T,H,Kv,hd,window",
    [
        (1, 32, 32, 4, 4, 32, None),  # MHA
        (2, 64, 64, 8, 2, 16, None),  # GQA 4:1
        (2, 64, 64, 4, 1, 32, None),  # MQA
        (1, 48, 48, 2, 2, 64, 16),  # SWA
        (1, 16, 64, 4, 2, 32, None),  # decode-ish: q block shorter than kv
        (3, 128, 128, 2, 1, 8, 32),
    ],
)
def test_flash_attention_sweep(B, S, T, H, Kv, hd, window, dtype):
    q = randn(B, S, H, hd, dtype=dtype)
    k = randn(B, T, Kv, hd, dtype=dtype)
    v = randn(B, T, Kv, hd, dtype=dtype)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    got = flash_attention_pallas(
        q, k, v, causal=True, window=window, blk_q=16, blk_k=16, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


def test_flash_attention_noncausal():
    q, k, v = randn(2, 32, 4, 16), randn(2, 32, 2, 16), randn(2, 32, 2, 16)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    got = flash_attention_pallas(q, k, v, causal=False, blk_q=16, blk_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_softmax_definition():
    """Against the literal softmax(QKᵀ/√d)V definition, not just the ref."""
    q, k, v = randn(1, 16, 2, 8), randn(1, 16, 2, 8), randn(1, 16, 2, 8)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(8)
    mask = np.tril(np.ones((16, 16), bool))
    s = jnp.where(mask[None, None], s, -1e30)
    want = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v)
    got = flash_attention_pallas(q, k, v, causal=True, blk_q=8, blk_k=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["pallas", "ops"])
def test_flash_attention_non_default_scale(impl):
    """A given ``scale`` replaces 1/√d (GQA, scale 1/d as in Granite's NoPE
    attention): kernel, wrapper and reference against the literal softmax."""
    from repro.kernels import ops

    hd, scale = 32, 1.0 / 32
    q, k, v = randn(2, 32, 4, hd, scale=4.0), randn(2, 32, 2, hd, scale=4.0), randn(2, 32, 2, hd)
    kr, vr = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, kr) * scale
    s = jnp.where(np.tril(np.ones((32, 32), bool))[None, None], s, -1e30)
    want = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), vr)
    np.testing.assert_allclose(
        np.asarray(ref.flash_attention_ref(q, k, v, scale=scale)), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    if impl == "pallas":
        got = flash_attention_pallas(q, k, v, scale=scale, blk_q=16, blk_k=8, interpret=True)
    else:
        got = ops.flash_attention(q, k, v, scale=scale, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    # the default stays 1/√d
    default = flash_attention_pallas(q, k, v, blk_q=16, blk_k=8, interpret=True)
    assert not np.allclose(np.asarray(default), np.asarray(want), atol=1e-3)


@settings(max_examples=10, deadline=None)
@given(
    bq=st.sampled_from([8, 16, 32, 64]),
    bk=st.sampled_from([8, 16, 32, 64]),
    scale=st.floats(min_value=0.1, max_value=8.0),
)
def test_flash_attention_block_shape_invariance(bq, bk, scale):
    """Property: result is independent of BlockSpec tiling and input scale
    doesn't break the online softmax."""
    q = randn(1, 64, 2, 16, scale=scale)
    k = randn(1, 64, 2, 16, scale=scale)
    v = randn(1, 64, 2, 16)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    got = flash_attention_pallas(q, k, v, causal=True, blk_q=bq, blk_k=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,C,blk", [(1, 16, 32, 32), (2, 64, 128, 64), (3, 33, 96, 32)])
def test_rglru_sweep(B, S, C, blk, dtype):
    x, r, i = randn(B, S, C, dtype=dtype), randn(B, S, C, dtype=dtype), randn(B, S, C, dtype=dtype)
    lam = randn(C)
    want_y, want_h = ref.rglru_ref(x, r, i, lam)
    got_y, got_h = rglru_pallas(x, r, i, lam, blk_c=blk, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got_y, np.float32), np.asarray(want_y, np.float32), **TOL[dtype]
    )
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h), **TOL[dtype])


def test_rglru_with_initial_state():
    x, r, i = randn(2, 8, 16), randn(2, 8, 16), randn(2, 8, 16)
    lam, h0 = randn(16), randn(2, 16)
    want_y, want_h = ref.rglru_ref(x, r, i, lam, h0=h0)
    got_y, got_h = rglru_pallas(x, r, i, lam, h0=h0, interpret=True)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h), rtol=2e-5, atol=2e-5)


def test_rglru_step_equals_scan():
    """Decode recurrence must continue the train-time scan exactly."""
    x, r, i = randn(2, 9, 16), randn(2, 9, 16), randn(2, 9, 16)
    lam = randn(16)
    want_y, want_h = ref.rglru_ref(x, r, i, lam)
    h = jnp.zeros((2, 16))
    for t in range(9):
        y_t, h = ref.rglru_step_ref(h, x[:, t], r[:, t], i[:, t], lam)
        np.testing.assert_allclose(np.asarray(y_t), np.asarray(want_y[:, t]), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h), rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(s=st.integers(min_value=1, max_value=32), c=st.sampled_from([8, 16, 64]))
def test_rglru_stability_property(s, c):
    """Property: |a_t| < 1 ⇒ outputs bounded by running max of inputs (up to
    the √(1-a²) normalization) — no blowup for any gate values."""
    x, r, i = randn(1, s, c, scale=3.0), randn(1, s, c, scale=3.0), randn(1, s, c, scale=3.0)
    lam = randn(c, scale=2.0)
    y, _ = ref.rglru_ref(x, r, i, lam)
    assert bool(jnp.all(jnp.isfinite(y)))
    assert float(jnp.max(jnp.abs(y))) <= float(jnp.max(jnp.abs(x))) * (s + 1)


# ---------------------------------------------------------------------------
# Mamba2 SSD
# ---------------------------------------------------------------------------


def ssd_inputs(B, S, H, P, G, N, dtype=jnp.float32):
    return (
        randn(B, S, H, P, dtype=dtype),
        jnp.asarray(RNG.uniform(1e-3, 0.1, size=(B, S, H)), jnp.float32),
        jnp.asarray(RNG.uniform(0, 2, size=(H,)), jnp.float32),
        randn(B, S, G, N, dtype=dtype),
        randn(B, S, G, N, dtype=dtype),
        randn(H),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,P,G,N,chunk",
    [(1, 32, 2, 16, 1, 8, 8), (2, 64, 4, 16, 2, 8, 16), (1, 128, 8, 32, 2, 16, 32)],
)
def test_ssd_sweep(B, S, H, P, G, N, chunk, dtype):
    x, dt, A_log, Bm, Cm, D = ssd_inputs(B, S, H, P, G, N, dtype)
    want_y, want_st = ref.ssd_ref(x, dt, A_log, Bm, Cm, D, chunk=chunk)
    got_y, got_st = ssd_pallas(x, dt, A_log, Bm, Cm, D, chunk=chunk, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got_y, np.float32), np.asarray(want_y, np.float32), **TOL[dtype]
    )
    np.testing.assert_allclose(np.asarray(got_st), np.asarray(want_st), rtol=1e-3, atol=1e-3)


def test_ssd_chunked_equals_sequential():
    """The chunked SSD must equal the token-by-token recurrence (the decode
    path) — the core state-space duality identity."""
    B, S, H, P, G, N = 2, 24, 2, 8, 1, 4
    x, dt, A_log, Bm, Cm, D = ssd_inputs(B, S, H, P, G, N)
    want_y, want_st = ref.ssd_ref(x, dt, A_log, Bm, Cm, D, chunk=8)
    st_ = jnp.zeros((B, H, P, N))
    for t in range(S):
        y_t, st_ = ref.ssd_step_ref(st_, x[:, t], dt[:, t], A_log, Bm[:, t], Cm[:, t], D)
        np.testing.assert_allclose(np.asarray(y_t), np.asarray(want_y[:, t]), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_), np.asarray(want_st), rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(chunk=st.sampled_from([4, 8, 16, 32]))
def test_ssd_chunk_size_invariance(chunk):
    """Property: the result must not depend on the chunking."""
    B, S, H, P, G, N = 1, 32, 2, 8, 1, 4
    x, dt, A_log, Bm, Cm, D = ssd_inputs(B, S, H, P, G, N)
    base, st0 = ref.ssd_ref(x, dt, A_log, Bm, Cm, D, chunk=S)
    got, st1 = ref.ssd_ref(x, dt, A_log, Bm, Cm, D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st0), rtol=2e-4, atol=2e-4)


def test_ssd_state_continuation():
    """Splitting a sequence and carrying state must be exact."""
    B, S, H, P, G, N = 1, 32, 2, 8, 1, 4
    x, dt, A_log, Bm, Cm, D = ssd_inputs(B, S, H, P, G, N)
    full, st_full = ref.ssd_ref(x, dt, A_log, Bm, Cm, D, chunk=8)
    ya, sa = ref.ssd_ref(x[:, :16], dt[:, :16], A_log, Bm[:, :16], Cm[:, :16], D, chunk=8)
    yb, sb = ref.ssd_ref(x[:, 16:], dt[:, 16:], A_log, Bm[:, 16:], Cm[:, 16:], D, chunk=8, state0=sa)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([ya, yb], 1)), np.asarray(full), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(np.asarray(sb), np.asarray(st_full), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Mamba2 SSD decode step, in place in the stacked state
# ---------------------------------------------------------------------------


def _ref_step_inplace(state, l, x, dt, A_log, Bt, Ct, D):
    """The reference's path: the layer's slice read, stepped, written back."""
    st_ = jax.lax.dynamic_index_in_dim(state, l, 0, keepdims=False)
    y, st_ = ref.ssd_step_ref(st_.astype(jnp.float32), x, dt, A_log, Bt, Ct, D)
    return y, jax.lax.dynamic_update_index_in_dim(state, st_.astype(state.dtype), l, 0)


@pytest.mark.parametrize(
    "L,B,H,P,N,G",
    [
        (3, 2, 64, 64, 16, 1),  # mamba2-1.3b's heads, the state cut to 16
        (2, 2, 128, 64, 16, 1),  # granite's: twice the heads
        (3, 8, 16, 32, 16, 1),  # 8 slots, as the code mix serves
        (2, 2, 16, 32, 16, 2),  # two B/C groups
    ],
)
def test_ssd_step_inplace_matches_reference(L, B, H, P, N, G):
    """Layer after layer in a scan, as the decode step runs it, the kernel's
    state equals the reference's bit for bit and y lies within one bf16 ulp
    of it; a step of one layer leaves every other layer as it was.

    Every f32 product of the update is exact on this data, so that the
    order of evaluation cannot move a last bit: dt lies on a grid of 1/1024
    (XLA associates the reference's dt·x·B differently by shape: on the CPU
    x·(dt·B) with one group, (dt·x)·B with two), and the state holds signed
    powers of two (the CPU compiler fuses h·exp(dt·A) + x·dt·B into one
    multiply-add or not, depending on what else it fused)."""
    rng = np.random.default_rng(7)
    signs = rng.choice([-1.0, 1.0], size=(L, B, H, P, N))
    state = jnp.asarray(signs * 2.0 ** rng.integers(-8, 3, size=(L, B, H, P, N)), jnp.bfloat16)
    per_layer = (
        jnp.asarray(rng.normal(size=(L, B, H, P)), jnp.bfloat16),
        jnp.asarray(rng.integers(1, 128, size=(L, B, H)) / 1024, jnp.float32),
        jnp.asarray(rng.uniform(0, 2, size=(L, H)), jnp.float32),
        jnp.asarray(rng.normal(size=(L, B, G, N)), jnp.bfloat16),
        jnp.asarray(rng.normal(size=(L, B, G, N)), jnp.bfloat16),
        jnp.asarray(rng.uniform(0.5, 1.5, size=(L, H)), jnp.float32),
    )
    kernel = functools.partial(ops.ssd_step_inplace, impl="pallas")

    def layers(step):
        def body(carry, p):
            st_, l = carry
            y, st_ = step(st_, l, *p)
            return (st_, l + 1), y

        (st_, _), y = jax.jit(lambda s: jax.lax.scan(body, (s, jnp.int32(0)), per_layer))(state)
        return np.asarray(st_.astype(jnp.float32)), np.asarray(y)

    want_st, want_y = layers(_ref_step_inplace)
    got_st, got_y = layers(kernel)
    np.testing.assert_array_equal(got_st, want_st)
    gap = np.abs(got_y.astype(np.float32) - want_y.astype(np.float32))
    assert np.all(gap <= np.spacing(np.abs(want_y)).astype(np.float32))

    one = np.asarray(jax.jit(kernel)(state, 1, *(p[1] for p in per_layer))[1].astype(jnp.float32))
    before = np.asarray(state.astype(jnp.float32))
    np.testing.assert_array_equal(np.delete(one, 1, axis=0), np.delete(before, 1, axis=0))
    assert not np.array_equal(one[1], before[1])


# ---------------------------------------------------------------------------
# causal conv1d
# ---------------------------------------------------------------------------


def test_causal_conv1d_state_continuation():
    x = randn(2, 12, 6)
    w = randn(4, 6)
    full, _ = ref.causal_conv1d_ref(x, w)
    ya, st = ref.causal_conv1d_ref(x[:, :7], w)
    yb, _ = ref.causal_conv1d_ref(x[:, 7:], w, state=st)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([ya, yb], 1)), np.asarray(full), rtol=1e-6, atol=1e-6
    )


def test_ops_dispatch_ref_on_cpu():
    from repro.kernels import ops

    q, k, v = randn(1, 16, 2, 8), randn(1, 16, 2, 8), randn(1, 16, 2, 8)
    out = ops.flash_attention(q, k, v)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_ops_pallas_impl_selectable():
    from repro.kernels import ops

    q, k, v = randn(1, 16, 2, 8), randn(1, 16, 2, 8), randn(1, 16, 2, 8)
    out = ops.flash_attention(q, k, v, impl="pallas")
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kernel", ["ssd", "rglru"])
def test_ops_pallas_gradients_equal_reference(kernel):
    """impl="pallas" is differentiable (custom_vjp: kernel forward, reference
    VJP backward) and its gradients equal the reference path's."""
    from repro.kernels import ops

    if kernel == "ssd":
        args = ssd_inputs(2, 32, 4, 16, 2, 8)

        def loss(impl, *a):
            y, st = ops.ssd(*a, chunk=8, impl=impl)
            return jnp.sum(y**2) + jnp.sum(st)
    else:
        args = (randn(2, 24, 128), randn(2, 24, 128), randn(2, 24, 128), randn(128), randn(2, 128))

        def loss(impl, *a):
            y, h = ops.rglru(*a, impl=impl)
            return jnp.sum(y**2) + jnp.sum(h)

    argnums = tuple(range(1, len(args) + 1))
    got = jax.grad(loss, argnums=argnums)("pallas", *args)
    want = jax.grad(loss, argnums=argnums)("ref", *args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_ssd_gradients_finite_when_chunk_decay_overflows(impl):
    """Over a 256-step chunk (mamba2's published chunk) the log-decay between
    far-apart steps passes exp's range; the masked upper triangle must not
    turn the gradient into NaN (0 · inf)."""
    from repro.kernels import ops

    x, _, _, Bm, Cm, D = ssd_inputs(1, 256, 2, 8, 1, 8)
    dt = jnp.asarray(RNG.uniform(0.05, 0.1, size=(1, 256, 2)), jnp.float32)
    A_log = jnp.log(jnp.asarray([8.0, 16.0]))  # |dt · A| >= 0.4 a step

    def loss(*a):
        y, st = ops.ssd(*a, chunk=256, impl=impl)
        return jnp.sum(y**2) + jnp.sum(st)

    grads = jax.grad(loss, argnums=tuple(range(6)))(x, dt, A_log, Bm, Cm, D)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
