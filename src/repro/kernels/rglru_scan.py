"""Pallas TPU RG-LRU scan (RecurrentGemma/Griffin recurrence).

TPU adaptation: the recurrence h_t = a_t·h_{t-1} + b_t is sequential in time
but embarrassingly parallel over channels.  The kernel tiles channels into
128-lane VMEM blocks (grid dim 1) and the sequence into blocks (grid dim 2,
sequential, h carried in VMEM scratch between them).  Gate math
(softplus/sigmoid/exp) is fused: a/b land in VMEM scratch and never
round-trip to HBM, and a fori_loop walks the block reading one f32 row of
each per step through the refs (h_t overwrites the spent b_t row; the block
is cast and stored whole) — the TPU-idiomatic replacement for a GPU warp
scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    x_ref, r_ref, i_ref, lam_ref, h0_ref, y_ref, hN_ref, a_scr, b_scr, h_scr, *, blk_s: int, c: float
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    lam = lam_ref[...].astype(jnp.float32)  # [1, blk_c]
    log_a = -c * jax.nn.softplus(lam) * jax.nn.sigmoid(r_ref[...].astype(jnp.float32))
    a_scr[...] = jnp.exp(log_a)  # [blk_s, blk_c]
    gated = jax.nn.sigmoid(i_ref[...].astype(jnp.float32)) * x_ref[...].astype(jnp.float32)
    b_scr[...] = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * gated

    def step(t, h):
        row = pl.ds(t, 1)
        h = a_scr[row, :] * h + b_scr[row, :]  # [1, blk_c]
        b_scr[row, :] = h  # b_t is spent: its row now holds h_t
        return h

    h = jax.lax.fori_loop(0, blk_s, step, h_scr[...])
    h_scr[...] = h
    hN_ref[...] = h
    y_ref[...] = b_scr[...].astype(y_ref.dtype)


def _block(n: int, cap: int, align: int) -> int:
    """Largest divisor of n that is <= cap and a multiple of align; n itself
    (the whole axis) when none is."""
    for b in range(min(cap, n), 0, -1):
        if n % b == 0 and b % align == 0:
            return b
    return n


@functools.partial(jax.jit, static_argnames=("blk_c", "blk_s", "interpret", "c"))
def rglru_pallas(
    x, r, i, lam, h0=None, *, blk_c: int = 128, blk_s: int = 256, c: float = 8.0, interpret: bool = False
):
    """x, r, i: [B, S, C]; lam: [C]; h0: [B, C] or None → (y [B,S,C], h_last [B,C])."""
    B, S, C = x.shape
    if h0 is None:
        h0 = jnp.zeros((B, C), jnp.float32)
    blk_c = _block(C, blk_c, 128)
    blk_s = _block(S, blk_s, 8)
    seq = pl.BlockSpec((None, blk_s, blk_c), lambda b, ci, si: (b, si, ci))
    row = pl.BlockSpec((None, 1, blk_c), lambda b, ci, si: (b, 0, ci))
    y, hN = pl.pallas_call(
        functools.partial(_kernel, blk_s=blk_s, c=c),
        grid=(B, C // blk_c, S // blk_s),
        in_specs=[seq, seq, seq, pl.BlockSpec((1, blk_c), lambda b, ci, si: (0, ci)), row],
        out_specs=[seq, row],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, C), x.dtype),
            jax.ShapeDtypeStruct((B, 1, C), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_s, blk_c), jnp.float32),
            pltpu.VMEM((blk_s, blk_c), jnp.float32),
            pltpu.VMEM((1, blk_c), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="rglru_scan",
    )(x, r, i, lam[None, :], h0[:, None, :])
    return y, hN[:, 0]
