"""Pallas TPU Mamba2 decode step, in place in the stacked state cache.

One token per slot and head: h' = h·exp(dt·A) + x·(dt·B) and
y = h'·C + D·x.  The kernel is given the whole stacked state leaf
[L, B, H, P, N] and the layer index as a scalar-prefetch operand, which the
state's index maps read.  The state input is aliased to the state output, so
a donated, loop-carried cache is updated where it lies: each (slot, head)
slice of layer ``l`` is read once, and h' is written back over it in the
same pass that computes y from it.

Blocks: head blocks × slot blocks of about 2 MB of state, chosen from the
shapes; a head block never straddles a B/C group, so the group's rows are
picked by the block's index map.  The grid runs over slot blocks inside a
head block, so y's block (every slot of the head block) is written in
consecutive grid steps and stored once.  Every operand but the state is
laid out with a head-block axis, so each block spans its array's last two
dims, as Mosaic's tiling asks, whatever the head block.  Inside a block
the heads go in chunks of 128 rows of (head, p), each a [128, N] tile of
h' in f32:

- x·(dt·B): x's block is transposed once, so that x[h, :] is a column to
  broadcast along the N lanes of head h's rows;
- y: the lane sums Σ_n h'·C run on the MXU as C·h'ᵀ, which leaves y
  lane-dense.  h' is split exactly into three bf16 parts (hi + mid + lo,
  eight bits each), so the bf16 passes sum exact products in f32: the f32
  h' is what y is reduced from, as in the reference; C is split too unless
  it is bf16 already.

Precision is ``ref.ssd_step_ref``'s: the slice is upcast to f32, y comes from
the f32 h' before h' is rounded to the state's dtype, and y is returned in
x's dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _largest_divisor

#: state bytes a grid step moves each way
_BLOCK_BYTES = 2 << 20
_VMEM_LIMIT = 64 << 20
#: rows of (head, p) in a chunk: one MXU tile of h'ᵀ, one lane-dense row of y
_ROWS = 128
_NT = (((1,), (1,)), ((), ()))  # contract the last dims: C·h'ᵀ


def _split3(v):
    """v (f32) as hi + mid + lo, each exact in bf16 (the top 8 bits of what
    is left: a mask of the f32 word, so no rounding)."""

    def top(u):
        return pltpu.bitcast(pltpu.bitcast(u, jnp.uint32) & jnp.uint32(0xFFFF0000), jnp.float32)

    hi = top(v)
    mid = top(v - hi)
    return [p.astype(jnp.bfloat16) for p in (hi, mid, v - hi - mid)]


def _kernel(l_ref, st_ref, x_ref, dt_ref, alog_ref, b_ref, c_ref, d_ref, y_ref, out_ref):
    # st/out [bB, bH, P, N] (the grid step's slots); every slot of the head
    # block: x/y [B, bH·P/128, 128] (lane-dense (head, p)), dt [B, 1, bH],
    # b/c [B, 1, N] (the block's group); A_log [1, bH]; D [H] in SMEM
    del l_ref  # read by the state's index map
    f32 = jnp.float32
    bB, bH, P, N = st_ref.shape
    hc = _ROWS // P  # heads in a chunk
    h_first = pl.program_id(0) * bH
    A = (-jnp.exp(alog_ref[...])).T  # [bH, 1]
    lane_head = lax.broadcasted_iota(jnp.int32, (1, _ROWS), 1) // P
    for i in range(bB):
        row = pl.program_id(1) * bB + i
        dt = dt_ref[row].T  # [bH, 1]
        da = jnp.exp(dt * A)
        dtb = dt * b_ref[row].astype(f32)  # [bH, N]
        x = x_ref[row].astype(f32)  # [r, 128]
        xt = x.T  # [128, r]: column j is chunk j's (head, p)
        c = c_ref[row]
        cs = [c] if c.dtype == jnp.bfloat16 else _split3(c.astype(f32))
        cs = [jnp.broadcast_to(part, (8, N)) for part in cs]
        ys = []
        for j, h0 in enumerate(range(0, bH, hc)):
            hs = slice(h0, h0 + hc)
            xb = xt[:, j : j + 1].reshape(hc, P, 1) * dtb[hs][:, None, :]
            h = st_ref[i, hs].astype(f32) * da[hs][:, :, None] + xb  # [hc, P, N]
            out_ref[i, hs] = h.astype(out_ref.dtype)
            parts = _split3(h.reshape(_ROWS, N))
            yc = sum(
                lax.dot_general(cp, hp, _NT, preferred_element_type=f32) for cp in cs for hp in parts
            )
            d = sum(jnp.where(lane_head == k, d_ref[h_first + h0 + k], 0.0) for k in range(hc))
            ys.append(yc[:1] + x[j : j + 1] * d)  # [1, 128]
        y = jnp.concatenate(ys, axis=0) if len(ys) > 1 else ys[0]
        y_ref[row] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_step_pallas(state, l, x_t, dt_t, A_log, B_t, C_t, D, *, interpret: bool = False):
    """state [L,B,H,P,N], l the layer, x_t [B,H,P], dt_t [B,H], A_log/D [H],
    B_t/C_t [B,G,N] → (y_t [B,H,P] in x_t's dtype, state with layer l
    advanced one step, in place)."""
    _, Bs, H, P, N = state.shape
    G = B_t.shape[1]
    if H % G:
        raise ValueError(f"ssd_step: {H} heads do not split into {G} groups")
    hpg = H // G
    per_head = P * N * state.dtype.itemsize
    bH = _largest_divisor(hpg, _BLOCK_BYTES // per_head)
    if _ROWS % P or (bH * P) % _ROWS:
        raise ValueError(
            f"ssd_step: heads of {P} in blocks of {bH} do not tile {_ROWS}-row chunks"
        )
    bB = _largest_divisor(Bs, _BLOCK_BYTES // (bH * per_head))
    nh, r = H // bH, bH * P // _ROWS

    def st_map(h, b, l):
        return l[0], b, h, 0, 0

    state_spec = pl.BlockSpec((None, bB, bH, P, N), st_map)
    row_spec = pl.BlockSpec((Bs, None, r, _ROWS), lambda h, b, l: (0, h, 0, 0))
    group_spec = pl.BlockSpec((Bs, None, 1, N), lambda h, b, l: (0, h * bH // hpg, 0, 0))
    y, state = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nh, Bs // bB),
            in_specs=[
                state_spec,
                row_spec,
                pl.BlockSpec((Bs, None, 1, bH), lambda h, b, l: (0, h, 0, 0)),
                pl.BlockSpec((None, 1, bH), lambda h, b, l: (h, 0, 0)),
                group_spec,
                group_spec,
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=[row_spec, state_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Bs, nh, r, _ROWS), x_t.dtype),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=interpret,
        name="ssd_step",
    )(
        jnp.reshape(l, (1,)).astype(jnp.int32),
        state,
        x_t.reshape(Bs, nh, r, _ROWS),
        dt_t.astype(jnp.float32).reshape(Bs, nh, 1, bH),
        A_log.astype(jnp.float32).reshape(nh, 1, bH),
        B_t.reshape(Bs, G, 1, N),
        C_t.reshape(Bs, G, 1, N),
        D.astype(jnp.float32),
    )
    return y.reshape(Bs, H, P), state
