"""Plain Granite-4.0-H reference: the language model's forward pass in
float32, the held experts' share of each MoE.

Written from the layer equations of HF ``GraniteMoeHybrid`` (config
``granitemoehybrid``), in straightforward ``jax.numpy`` with every matrix
product at ``highest`` precision.  No kernel, cache or batching; it imports
nothing of the program under test, and takes the SSD scan from
``bench/reference.py``.

    x = 12 · E[tok]
    each layer:  x += 0.22 · mixer(rms(x));  h = rms(x)
                 x += 0.22 · (Σ_{e ∈ top10, e held} g_e · SwiGLU_e(h) + SwiGLU_shared(h))
    logits = rms(x) · Eᵀ / 16

The gates ``g`` are the softmax over the 10 largest of the 72 router
logits.  Mamba2 mixer: z, x, B, C, dt projections → causal depthwise conv
with bias over (x|B|C), SiLU → SSD + D·x → rms(y · SiLU(z)) · w → out
projection.  Attention mixer: GQA, no position embedding, softmax(q·kᵀ ·
attention_multiplier + causal mask)·v, computed in blocks of queries.

Departures, as the program runs the model: norm gains stored as offsets
from 1 (``rms(x) · (1 + g)``); only the held experts' part of each MoE, the
share of one chip in the configuration's deployment.  The weights are held
as their bfloat16 values and each layer's are upcast when it runs.

``precision="fp8"`` is the control: the operands of the projections (of
both mixers and the router), of the experts and of the head are rounded to
float8 e4m3 with a per-tensor scale, the step below the configuration's
bfloat16.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp

from bench.reference import _fp8, ssd

#: queries per block of the attention (fewer in a shorter sequence)
Q_BLOCK = 256
HI = jax.lax.Precision.HIGHEST


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def _mm(a, w, precision: str):
    if precision == "fp8":
        a, w = _fp8(a), _fp8(w)
    return jnp.dot(a, w, precision=HI)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _mamba(z: dict, precision: str, h, p):
    S = h.shape[0]
    di, H, P, G, N, K = z["di"], z["H"], z["P"], z["G"], z["N"], z["K"]
    zg = _mm(h, p["wz"], precision)
    xbc = jnp.concatenate([_mm(h, p[k], precision) for k in ("wx", "wB", "wC")], axis=-1)
    dt = jax.nn.softplus(_mm(h, p["wdt"], precision) + p["dt_bias"])
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
    xbc = sum(padded[k : k + S] * p["conv_w"][k] for k in range(K))
    xbc = jax.nn.silu(xbc + p["conv_b"] if "conv_b" in p else xbc)
    xs, Bm, Cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    xs = xs.reshape(S, H, P)
    y = ssd(xs, dt, -jnp.exp(p["A_log"]), Bm.reshape(S, G, N), Cm.reshape(S, G, N))
    y = (y + xs * p["D"][:, None]).reshape(S, di)
    y = _rms(y * jax.nn.silu(zg), p["norm_g"], z["eps"])
    return _mm(y, p["wo"], precision)


def _attention(z: dict, precision: str, h, p):
    S, d = h.shape
    Hq, Kv, hd = z["Hq"], z["Kv"], z["hd"]
    q = _mm(h, p["wq"].reshape(d, Hq * hd), precision).reshape(S, Kv, Hq // Kv, hd)
    k = _mm(h, p["wk"].reshape(d, Kv * hd), precision).reshape(S, Kv, hd)
    v = _mm(h, p["wv"].reshape(d, Kv * hd), precision).reshape(S, Kv, hd)
    nq = min(Q_BLOCK, S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * nq, nq, 0)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HI) * z["attn_mult"]
        mask = jnp.arange(S)[None, :] <= i * nq + jnp.arange(nq)[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v, precision=HI)

    out = jax.lax.map(block, jnp.arange(S // nq)).reshape(S, Hq * hd)
    return _mm(out, p["wo"].reshape(Hq * hd, d), precision)


def _moe(z: dict, precision: str, h, p):
    """The held experts' part of the layer, and the shared expert."""
    logits = _mm(h, p["router"], precision)
    top, ids = jax.lax.top_k(logits, z["k"])
    gates = jax.nn.softmax(top, axis=-1)

    def expert(out, e):  # one held expert on every token, at its gate (0 if not chosen)
        eid, wg, wu, wd = e
        g = jnp.sum(jnp.where(ids == eid, gates, 0.0), axis=-1)
        a = jax.nn.silu(_mm(h, wg, precision)) * _mm(h, wu, precision)
        return out + g[:, None] * _mm(a, wd, precision), None

    held = (jnp.asarray(z["held"]), p["w_gate"], p["w_up"], p["w_down"])
    out, _ = jax.lax.scan(expert, jnp.zeros_like(h), held)
    a = jax.nn.silu(_mm(h, p["shared_gate"], precision)) * _mm(h, p["shared_up"], precision)
    return out + _mm(a, p["shared_down"], precision)


def hidden(z: dict, w: dict, tokens, precision: str = "f32"):
    """The last layer's output at every position of one sequence, tokens [S]
    (S a multiple of the SSD's and the attention's blocks; trailing
    padding changes nothing before it).  Consecutive layers of one kind
    run as one scan over their weights."""
    x = w["embed"]["tok"][tokens].astype(jnp.float32) * z["emb_mult"]
    seen = {"mamba": 0, "attention": 0}
    layer = 0
    for kind, group in itertools.groupby(z["types"]):
        n = len(list(group))
        i = seen[kind]
        seen[kind] += n
        mixer = _mamba if kind == "mamba" else _attention
        ws = jax.tree_util.tree_map(lambda a: a[i : i + n], w["mamba" if kind == "mamba" else "attn"])
        moe = jax.tree_util.tree_map(lambda a: a[layer : layer + n], w["moe"])
        layer += n

        def body(x, ps, mixer=mixer):
            p, q = _f32(ps[0]), _f32(ps[1])
            x = x + z["res_mult"] * mixer(z, precision, _rms(x, p["ln"]["w"], z["eps"]), p)
            return x + z["res_mult"] * _moe(z, precision, _rms(x, q["ln"]["w"], z["eps"]), q), None

        x, _ = jax.lax.scan(body, x, (ws, moe))
    return x


def logits_at(z: dict, w: dict, tokens, positions, precision: str = "f32"):
    """Logits over the real vocabulary at ``positions`` of one sequence.
    tokens [S], positions [M] → [M, V]."""
    x = hidden(z, w, tokens, precision)
    x = _rms(x[positions], w["ln_f"]["w"].astype(jnp.float32), z["eps"])
    head = w["embed"]["tok"].astype(jnp.float32).T
    return _mm(x, head, precision)[:, : z["V"]] / z["logit_div"]


def _key(z: dict) -> tuple:
    return tuple(sorted(z.items()))


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
    control puts first).  Rows run one at a time.  tokens [R,S] (S a
    multiple of ``ROW_MULTIPLE``, or of the SSD's chunk below it),
    positions and served [R,M]."""
    with jax.default_matmul_precision("highest"):
        return _gaps_fn(_key(z), control)(w, tokens, positions, served)


#: a row's length is a multiple of this (the SSD's chunk and the query block)
ROW_MULTIPLE = Q_BLOCK
