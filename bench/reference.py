"""Plain Mamba2 reference: the language model's forward pass in float32.

Written from the Mamba2 paper (arXiv:2405.21060, Listing 1 "SSD minimal")
and the block as this repository serves it, in straightforward
``jax.numpy`` with every matrix product at ``highest`` precision.  No
kernel, cache or batching; it imports nothing of the program under test.

Block: RMSNorm(x)·(1 + g) → z, x, B, C, dt projections → causal depthwise
conv over (x|B|C), SiLU → SSD (chunked form of the scan) + D·x →
RMSNorm(y·SiLU(z))·(1 + g) → out projection, added to the residual.
Departures from the published block, as the program runs it: no conv bias,
no projection biases, and norm gains stored as offsets from 1.

``precision="fp8"`` is the control: every operand of the dense projections
and of the output head is rounded to float8 e4m3 with a per-tensor scale,
the step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: the reference's own chunk for the SSD form (the program's is 256)
CHUNK = 64
EPS = 1e-6


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * (1.0 + g)


def _fp8(t):
    scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 448.0
    return (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, precision: str):
    if precision == "fp8":
        a, w = _fp8(a), _fp8(w)
    return jnp.dot(a, w, precision=jax.lax.Precision.HIGHEST)


def ssd(x, dt, A, Bm, Cm):
    """Scan y_t = C_t · h_t, h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_tᵀ, zero
    initial state, in its chunked (quadratic within a chunk) form.
    x [S,H,P], dt [S,H], A [H], Bm/Cm [S,G,N]; S a multiple of CHUNK."""
    S, H, P = x.shape
    G = Bm.shape[1]
    c, Lc = S // CHUNK, CHUNK
    hi = jax.lax.Precision.HIGHEST
    Bh = jnp.repeat(Bm, H // G, axis=1).reshape(c, Lc, H, -1)
    Ch = jnp.repeat(Cm, H // G, axis=1).reshape(c, Lc, H, -1)
    xc = x.reshape(c, Lc, H, P)
    dtc = dt.reshape(c, Lc, H)
    cum = jnp.cumsum(dtc * A, axis=1)  # [c, L, H]
    seg = cum[:, :, None, :] - cum[:, None, :, :]  # [c, i, j, H]
    causal = (jnp.arange(Lc)[:, None] >= jnp.arange(Lc)[None, :])[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    CB = jnp.einsum("cihn,cjhn->cijh", Ch, Bh, precision=hi)
    y = jnp.einsum("cijh,cjh,cjhp->cihp", CB * decay, dtc, xc, precision=hi)
    # state at the end of each chunk, then carried across chunks
    to_end = jnp.exp(cum[:, -1:, :] - cum) * dtc  # [c, L, H]
    chunk_states = jnp.einsum("cjhn,cjh,cjhp->chpn", Bh, to_end, xc, precision=hi)

    def carry(h, inp):
        st, last = inp
        return h * jnp.exp(last)[:, None, None] + st, h

    _, entering = jax.lax.scan(carry, jnp.zeros_like(chunk_states[0]), (chunk_states, cum[:, -1, :]))
    y = y + jnp.einsum("cihn,chpn,cih->cihp", Ch, entering, jnp.exp(cum), precision=hi)
    return y.reshape(S, H, P)


def _block(z: dict, precision: str, x, p):
    S = x.shape[0]
    di, H, P, G, N, K = z["di"], z["H"], z["P"], z["G"], z["N"], z["K"]
    h = _rms(x, p["ln"]["w"])
    zg = _mm(h, p["wz"], precision)
    xbc = jnp.concatenate([_mm(h, p[k], precision) for k in ("wx", "wB", "wC")], axis=-1)
    dt = jax.nn.softplus(_mm(h, p["wdt"], precision) + p["dt_bias"])
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
    xbc = jax.nn.silu(sum(padded[k : k + S] * p["conv_w"][k] for k in range(K)))
    xs, Bm, Cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    xs = xs.reshape(S, H, P)
    y = ssd(xs, dt, -jnp.exp(p["A_log"]), Bm.reshape(S, G, N), Cm.reshape(S, G, N))
    y = (y + xs * p["D"][:, None]).reshape(S, di)
    y = _rms(y * jax.nn.silu(zg), p["norm_g"])
    return x + _mm(y, p["wo"], precision), None


def logits_at(z: dict, w: dict, tokens, positions, precision: str = "f32"):
    """Logits over the real vocabulary at ``positions`` of one sequence.
    tokens [S] (S a multiple of CHUNK; trailing padding changes nothing
    before it), positions [M] → [M, V]."""
    x = w["embed"]["tok"][tokens]
    x, _ = jax.lax.scan(functools.partial(_block, z, precision), x, w["blocks"])
    x = _rms(x[positions], w["ln_f"]["w"])
    head = w["embed"]["tok"].T if z["tied"] else w["embed"]["head"]
    return _mm(x, head, precision)[:, : z["V"]]


@functools.lru_cache(maxsize=None)
def _gaps_fn(zkey: tuple, control: bool):
    z = dict(zkey)

    @jax.jit
    def gaps(w, tokens, positions, served):
        def row(args):
            t, pos, tok = args
            ref = logits_at(z, w, t, pos)
            best = jnp.max(ref, axis=-1)
            out = {"served": best - jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]}
            if control:
                low = jnp.argmax(logits_at(z, w, t, pos, "fp8"), axis=-1)
                out["control"] = best - jnp.take_along_axis(ref, low[:, None], axis=-1)[:, 0]
            return out

        return jax.lax.map(row, (tokens, positions, served))

    return gaps


def logit_gaps(z: dict, w: dict, tokens, positions, served, control: bool = False) -> dict:
    """Per row and position, how far the reference's logit of each served
    token lies below its best (and, with ``control``, of the token the fp8
    control puts first).  Rows are run one at a time so that the reference
    fits beside nothing else on the chip.  tokens [R,S], positions and
    served [R,M] → {"served": [R,M], "control": [R,M]}."""
    with jax.default_matmul_precision("highest"):
        return _gaps_fn(tuple(sorted(z.items())), control)(w, tokens, positions, served)

