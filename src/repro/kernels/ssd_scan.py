"""Pallas TPU Mamba2 SSD (state-space duality) chunked scan.

TPU adaptation of the SSD algorithm: per (batch, head) the sequence is cut
into chunks; within a chunk the quadratic "attention-like" form runs on the
MXU ([chunk × N] · [N × chunk] and [chunk × chunk] · [chunk × P] tiles), and
the O(1) inter-chunk state (held transposed, [N × P]) is carried in VMEM
scratch across the innermost grid dimension — the recurrence never leaves
the core.

Layout: the kernel works heads-major (x/y [B,H,S,P], B/C [B,G,S,N],
dt [B,H,1,S]) so every block's last two dims are (chunk, P|N) or (1, chunk)
— the TPU tiling rule — and the per-head scalars A_log and D sit whole in
SMEM, indexed by the head's grid position.  The wrapper keeps the
sequence-major interface of ``ref.ssd_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, d_ref, y_ref, st_ref, st_scr, *, chunk: int):
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        st_scr[...] = jnp.zeros_like(st_scr)

    x = x_ref[...].astype(jnp.float32)  # [L, P]
    dt_row = dt_ref[...].astype(jnp.float32)  # [1, L]
    A = -jnp.exp(alog_ref[h])  # per-head scalar from SMEM
    Bm = b_ref[...].astype(jnp.float32)  # [L, N]
    Cm = c_ref[...].astype(jnp.float32)  # [L, N]

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = jj <= ii
    a_row = dt_row * A  # [1, L] log-decay per step
    # column forms by masked lane reductions (no vector transposes)
    dt_col = jnp.sum(jnp.where(ii == jj, dt_row, 0.0), axis=1, keepdims=True)  # [L, 1]
    cum = jnp.sum(jnp.where(lower, a_row, 0.0), axis=1, keepdims=True)  # [L, 1] inclusive cumsum
    cum_row = jnp.sum(jnp.where(ii == jj, cum, 0.0), axis=0, keepdims=True)  # [1, L]
    total = jnp.sum(a_row, axis=1, keepdims=True)  # [1, 1]

    # intra-chunk quadratic form (lower triangular)
    LT = jnp.where(lower, jnp.exp(cum - cum_row), 0.0)
    CB = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [L, L]
    W = CB * LT * dt_row
    y = jnp.dot(W, x, preferred_element_type=jnp.float32)  # [L, P]
    # inter-chunk: contribution of the state entering this chunk
    st = st_scr[...]  # [N, P]
    y += jnp.exp(cum) * jnp.dot(Cm, st, preferred_element_type=jnp.float32)
    # state update for the next chunk
    xw = x * (dt_col * jnp.exp(total - cum))  # [L, P]
    st = st * jnp.exp(total) + jax.lax.dot_general(
        Bm, xw, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [N, P]
    st_scr[...] = st
    y_ref[...] = (y + x * d_ref[h]).astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(2) - 1)
    def _final():
        st_ref[...] = st


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, dt, A_log, Bm, Cm, D, *, chunk: int = 128, interpret: bool = False):
    """Shapes as ``ssd_ref`` (zero initial state) → (y [B,S,H,P], final state
    [B,H,P,N] f32)."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = H // G
    assert S % chunk == 0, f"S={S} % chunk={chunk}"
    nc = S // chunk
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    y, st = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((None, None, chunk, P), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((None, None, 1, chunk), lambda b, h, ci: (b, h, 0, ci)),
            smem,
            pl.BlockSpec((None, None, chunk, N), lambda b, h, ci: (b, h // hpg, ci, 0)),
            pl.BlockSpec((None, None, chunk, N), lambda b, h, ci: (b, h // hpg, ci, 0)),
            smem,
        ],
        out_specs=[
            pl.BlockSpec((None, None, chunk, P), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((None, None, N, P), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="ssd_scan",
    )(
        x.transpose(0, 2, 1, 3),
        dt.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :],
        A_log.astype(jnp.float32),
        Bm.transpose(0, 2, 1, 3),
        Cm.transpose(0, 2, 1, 3),
        D.astype(jnp.float32),
    )
    return y.transpose(0, 2, 1, 3), st.transpose(0, 1, 3, 2)
