"""Pallas TPU flash attention (causal/windowed GQA).

TPU-native adaptation (not a CUDA port): the grid's innermost dimension
iterates KV blocks sequentially while q/m/l/acc live in VMEM scratch — the
online-softmax accumulator pattern that keeps the working set in VMEM and
feeds the MXU [blk_q × d] · [d × blk_k] tiles.  The call works heads-major
([B,H,S,d]; the wrapper transposes), so each block's last two dims are
(blk, d) with d the whole head dim — legal TPU tiling for any head_dim.
GQA is handled in the index maps: the KV block index is ``h // (H // Kv)``,
so no KV replication in memory.

Block sizes default to 128×128 (MXU-native); the wrapper shrinks them to the
largest divisor for small test shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    blk_q: int,
    blk_k: int,
    nk: int,
    causal: bool,
    window: Optional[int],
    q_offset: int,
    scale: float,
):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)  # [blk_q, d]
    k = k_ref[...].astype(jnp.float32)  # [blk_k, d]
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale

    qi = pl.program_id(2)
    qpos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0) + q_offset
    kpos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    mask = jnp.ones((blk_q, blk_k), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]  # [blk_q, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        lsum = l_scr[...]
        # fully-masked rows (can't happen for causal q_offset>=0, but keep safe)
        denom = jnp.where(lsum == 0.0, 1.0, lsum)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _largest_divisor(n: int, cap: int) -> int:
    for b in range(min(cap, n), 0, -1):
        if n % b == 0:
            return b
    return 1


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "blk_q", "blk_k", "interpret")
)
def flash_attention_pallas(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    blk_q: int = 128,
    blk_k: int = 128,
    interpret: bool = False,
):
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    blk_q = _largest_divisor(S, blk_q)
    blk_k = _largest_divisor(T, blk_k)
    nq, nk = S // blk_q, T // blk_k
    grid = (B, H, nq, nk)
    kern = functools.partial(
        _kernel,
        blk_q=blk_q,
        blk_k=blk_k,
        nk=nk,
        causal=causal,
        window=window,
        q_offset=T - S,
        scale=1.0 / (hd**0.5) if scale is None else scale,
    )
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, blk_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, blk_k, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((None, None, blk_k, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, blk_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_attention",
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)
