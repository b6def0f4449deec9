"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each ``*_ref`` is the semantic definition; kernel tests sweep shapes/dtypes
and assert allclose against these.  The model zoo calls kernels.ops, which
dispatches to these refs on CPU and to the Pallas kernels on TPU.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Flash attention (causal / windowed GQA)
# ---------------------------------------------------------------------------


def flash_attention_ref(
    q, k, v, *, causal: bool = True, window: Optional[int] = None, scale: Optional[float] = None
):
    """Materialized-scores attention. q:[B,S,H,d] k/v:[B,T,Kv,d] → [B,S,H,d];
    scores scaled by ``scale`` (default 1/√d)."""
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    qg = q.reshape(B, S, Kv, G, hd)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(hd, jnp.float32)) if scale is None else s * scale
    qi = jnp.arange(S)[:, None] + (T - S)
    kj = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kj <= qi
    if window is not None:
        mask &= kj > qi - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", p, v).reshape(B, S, H, hd)


# ---------------------------------------------------------------------------
# RG-LRU linear recurrence (Griffin / RecurrentGemma)
# ---------------------------------------------------------------------------


def rglru_scan_ref(a, b, h0=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """h_t = a_t ⊙ h_{t-1} + b_t over axis 1. a,b: [B,S,C] → (h, h_last).

    Associative formulation — on TPU this parallelizes (log-depth) instead of
    the GPU-style sequential warp scan (DESIGN.md hardware adaptation).
    """
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    if h0 is not None:
        bf = bf.at[:, 0].add(af[:, 0] * h0.astype(jnp.float32))

    def op(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    _, h = jax.lax.associative_scan(op, (af, bf), axis=1)
    return h.astype(a.dtype), h[:, -1]


def rglru_gates_ref(x, r, i, lam, c: float = 8.0):
    """RG-LRU gate math: a_t = exp(-c·softplus(Λ)·σ(r_t)); b_t = √(1-a²)·(σ(i_t)·x_t)."""
    log_a = -c * jax.nn.softplus(lam.astype(jnp.float32)) * jax.nn.sigmoid(
        r.astype(jnp.float32)
    )
    a = jnp.exp(log_a)
    gated = jax.nn.sigmoid(i.astype(jnp.float32)) * x.astype(jnp.float32)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * gated
    return a, b


def rglru_ref(x, r, i, lam, h0=None, c: float = 8.0):
    a, b = rglru_gates_ref(x, r, i, lam, c)
    h, h_last = rglru_scan_ref(a, b, h0)
    return h.astype(x.dtype), h_last


def rglru_step_ref(h, x_t, r_t, i_t, lam, c: float = 8.0):
    """Single decode step: returns (y_t, h')."""
    a, b = rglru_gates_ref(x_t[:, None], r_t[:, None], i_t[:, None], lam, c)
    h_new = a[:, 0] * h.astype(jnp.float32) + b[:, 0]
    return h_new.astype(x_t.dtype), h_new


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality, chunked)
# ---------------------------------------------------------------------------


def ssd_ref(x, dt, A_log, Bm, Cm, D, chunk: int = 64, state0=None):
    """Chunked SSD. Shapes:
      x: [B,S,H,P]  dt: [B,S,H] (post-softplus)  A_log: [H]
      Bm, Cm: [B,S,G,N]  D: [H]
    Returns (y [B,S,H,P], final state [B,H,P,N]).
    """
    Bsz, S, H, Pd = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = H // G
    assert S % chunk == 0, f"seq {S} must divide chunk {chunk}"
    nc = S // chunk
    A = -jnp.exp(A_log.astype(jnp.float32))  # [H]
    a = dt.astype(jnp.float32) * A  # [B,S,H] (log-decay per step)

    xf = x.astype(jnp.float32).reshape(Bsz, nc, chunk, H, Pd)
    dtf = dt.astype(jnp.float32).reshape(Bsz, nc, chunk, H)
    af = a.reshape(Bsz, nc, chunk, H)
    Bf = Bm.astype(jnp.float32).reshape(Bsz, nc, chunk, G, N)
    Cf = Cm.astype(jnp.float32).reshape(Bsz, nc, chunk, G, N)
    # broadcast groups → heads
    Bh = jnp.repeat(Bf, hpg, axis=3)  # [B,nc,L,H,N]
    Ch = jnp.repeat(Cf, hpg, axis=3)

    cum = jnp.cumsum(af, axis=2)  # [B,nc,L,H]
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [B,nc,i,j,H]
    ii, jj = jnp.meshgrid(jnp.arange(chunk), jnp.arange(chunk), indexing="ij")
    # mask before exp: above the diagonal seg > 0 can overflow, and the
    # gradient of where(mask, exp(seg), 0) there is 0 · inf = NaN
    LT = jnp.exp(jnp.where((jj <= ii)[None, None, :, :, None], seg, -jnp.inf))
    # intra-chunk: y[i] = Σ_j C_i·B_j · L[i,j] · dt_j · x_j
    CB = jnp.einsum("bcihn,bcjhn->bcijh", Ch, Bh)  # [B,nc,i,j,H]
    W = CB * LT * dtf[:, :, None, :, :]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", W, xf)
    # chunk-boundary states
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # [B,nc,L,H]
    chunk_state = jnp.einsum(
        "bclh,bclhn,bclhp->bchpn", dtf * decay_to_end, Bh, xf
    )  # [B,nc,H,P,N]
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # [B,nc,H]

    s0 = (
        jnp.zeros((Bsz, H, Pd, N), jnp.float32)
        if state0 is None
        else state0.astype(jnp.float32)
    )

    def body(carry, inp):
        st = carry
        cs, cd = inp  # [B,H,P,N], [B,H]
        new = st * cd[:, :, None, None] + cs
        return new, st  # emit state *entering* this chunk

    chunk_states = jnp.moveaxis(chunk_state, 1, 0)
    chunk_decays = jnp.moveaxis(chunk_decay, 1, 0)
    final, entering = jax.lax.scan(body, s0, (chunk_states, chunk_decays))
    entering = jnp.moveaxis(entering, 0, 1)  # [B,nc,H,P,N]
    # inter-chunk contribution: y[i] += exp(cum_i) · C_i · state_entering
    y_inter = jnp.einsum(
        "bclh,bclhn,bchpn->bclhp", jnp.exp(cum), Ch, entering
    )
    y = (y_intra + y_inter).reshape(Bsz, S, H, Pd)
    y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), final


def ssd_step_ref(state, x_t, dt_t, A_log, B_t, C_t, D):
    """Single decode step.
      state: [B,H,P,N]  x_t: [B,H,P]  dt_t: [B,H]  B_t/C_t: [B,G,N]
    Returns (y_t [B,H,P], state').
    """
    H = x_t.shape[1]
    G = B_t.shape[1]
    hpg = H // G
    A = -jnp.exp(A_log.astype(jnp.float32))
    da = jnp.exp(dt_t.astype(jnp.float32) * A)  # [B,H]
    Bh = jnp.repeat(B_t.astype(jnp.float32), hpg, axis=1)  # [B,H,N]
    Ch = jnp.repeat(C_t.astype(jnp.float32), hpg, axis=1)
    xb = jnp.einsum(
        "bh,bhp,bhn->bhpn", dt_t.astype(jnp.float32), x_t.astype(jnp.float32), Bh
    )
    state = state.astype(jnp.float32) * da[:, :, None, None] + xb
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch)
    y = y + x_t.astype(jnp.float32) * D.astype(jnp.float32)[None, :, None]
    return y.astype(x_t.dtype), state


# ---------------------------------------------------------------------------
# Causal depthwise conv1d (SSM/RG-LRU temporal conv)
# ---------------------------------------------------------------------------


def causal_conv1d_ref(x, w, state=None):
    """x: [B,S,C], w: [K,C] depthwise causal conv.
    state: [B,K-1,C] trailing context (decode). Returns (y, new_state)."""
    K = w.shape[0]
    pad = (
        jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
        if state is None
        else state.astype(x.dtype)
    )
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i : i + x.shape[1]] * w[i][None, None, :] for i in range(K))
    return y.astype(x.dtype), xp[:, -(K - 1) :] if K > 1 else pad
