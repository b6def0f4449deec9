"""Shared neural building blocks: norms, RoPE, GQA attention, gated MLPs.

All functions are pure (params in, arrays out) and jit/scan/shard_map
friendly.  Attention supports causal, sliding-window (SWA), local, cross and
decode-with-cache masking in one code path — the mask offset handles the
"query block sits at the end of a longer KV" decode geometry.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .param import Spec

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def layernorm(x, w, b, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def norm_spec(cfg: ModelConfig, stacked: Optional[int] = None) -> dict:
    lead = (stacked,) if stacked else ()
    lax = ("layers",) if stacked else ()
    if cfg.norm == "layernorm":
        return {
            "w": Spec(lead + (cfg.d_model,), lax + ("embed",), "ones"),
            "b": Spec(lead + (cfg.d_model,), lax + ("embed",), "zeros"),
        }
    return {"w": Spec(lead + (cfg.d_model,), lax + ("embed",), "zeros")}


def apply_norm(cfg: ModelConfig, p: dict, x):
    if cfg.norm == "layernorm":
        return layernorm(x, p["w"], p["b"])
    return rmsnorm(x, p["w"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float):
    """x: [B, S, H, hd]; positions: [B, S] or [S]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA; causal / window / cross / decode)
# ---------------------------------------------------------------------------


def attend(
    q,  # [B, S, H, hd]
    k,  # [B, T, Kv, hd]
    v,  # [B, T, Kv, hd]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: Optional[jnp.ndarray] = None,  # absolute position of q[.,0]
    kv_len: Optional[jnp.ndarray] = None,  # valid prefix length of k/v
    scale: Optional[float] = None,  # score multiplier; None: 1/√hd
):
    """Grouped-query attention with unified masking.

    ``q_offset`` positions the query block inside the key timeline (decode:
    q_offset = cache_len); ``kv_len`` masks cache slots beyond the valid
    prefix.  fp32 softmax for stability.
    """
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    qg = q.reshape(B, S, Kv, G, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    if scale is None:
        scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    else:
        scores = scores * scale

    qi = jnp.arange(S)[:, None]  # [S, 1]
    kj = jnp.arange(T)[None, :]  # [1, T]
    if q_offset is None:
        off = jnp.asarray(T - S)
    else:
        off = q_offset
    qabs = qi + off  # absolute query positions
    mask = jnp.ones((S, T), dtype=bool)
    if causal:
        mask &= kj <= qabs
    if window is not None:
        mask &= kj > qabs - window
    mask_b = mask[None, :, :]
    if kv_len is not None:
        kvl = jnp.asarray(kv_len)
        kvl = kvl.reshape(-1, 1, 1) if kvl.ndim else kvl.reshape(1, 1, 1)
        mask_b = mask_b & (kj[None] < kvl)
    scores = jnp.where(mask_b[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H, hd)


def attn_specs(cfg: ModelConfig, stacked: Optional[int] = None, cross: bool = False) -> dict:
    # padded head counts (head_pad_to) let 40/56-head configs shard over the
    # 16-way model axis; pad weights are extra capacity, zero-cost to useful
    # math semantics at init (§Perf hillclimb, EXPERIMENTS.md)
    d, H, Kv, hd = cfg.d_model, cfg.padded_heads, cfg.padded_kv_heads, cfg.head_dim_
    lead = (stacked,) if stacked else ()
    lax = ("layers",) if stacked else ()
    s = {
        "wq": Spec(lead + (d, H, hd), lax + ("embed", "heads", "head_dim")),
        "wk": Spec(lead + (d, Kv, hd), lax + ("embed", "kv_heads", "head_dim")),
        "wv": Spec(lead + (d, Kv, hd), lax + ("embed", "kv_heads", "head_dim")),
        "wo": Spec(lead + (H, hd, d), lax + ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias and not cross:
        s["bq"] = Spec(lead + (H, hd), lax + ("heads", "head_dim"), "zeros")
        s["bk"] = Spec(lead + (Kv, hd), lax + ("kv_heads", "head_dim"), "zeros")
        s["bv"] = Spec(lead + (Kv, hd), lax + ("kv_heads", "head_dim"), "zeros")
    return s


def qkv(cfg: ModelConfig, p: dict, x, positions=None, use_rope: bool = True):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if use_rope and positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_out(p: dict, ctx):
    return jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])


def attend_chunked(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    chunk: int = 2048,
):
    """Flash-style attention: scan KV blocks with an online softmax.

    Never materializes the full [S, T] score tensor — the live score block is
    [S, chunk].  This is the jnp analogue of kernels/flash_attention.py (the
    Pallas kernel is the TPU runtime path; this one is what the dry-run
    lowers so the HLO byte counts reflect the blocked structure).
    """
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    if T % chunk:
        chunk = T  # fallback: single block
    G = H // Kv
    qg = q.reshape(B, S, Kv, G, hd)
    nb = T // chunk
    kb = jnp.moveaxis(k.reshape(B, nb, chunk, Kv, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, chunk, Kv, hd), 1, 0)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qpos = jnp.arange(S)[:, None] + (T - S)

    def body(carry, blk):
        m, lsum, acc = carry
        kc, vc, j0 = blk
        s = jnp.einsum("bskgd,btkd->bkgst", qg, kc).astype(jnp.float32) * scale
        kpos = j0 + jnp.arange(chunk)[None, :]
        mask = jnp.ones((S, chunk), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        lsum = lsum * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p.astype(q.dtype), vc
        ).astype(jnp.float32)
        return (m_new, lsum, acc), None

    m0 = jnp.full((B, Kv, G, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Kv, G, S), jnp.float32)
    a0 = jnp.zeros((B, Kv, G, S, hd), jnp.float32)
    offs = jnp.arange(nb) * chunk
    (m, lsum, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, offs))
    out = (acc / jnp.maximum(lsum, 1e-30)[..., None]).astype(q.dtype)
    return jnp.moveaxis(out, 3, 1).reshape(B, S, H, hd)


def attend_cfg(cfg: ModelConfig, q, k, v, *, causal: bool = True, window: Optional[int] = None):
    """Train/prefill attention with the config-selected implementation."""
    if cfg.attn_impl == "chunked" and k.shape[1] > cfg.attn_chunk:
        return attend_chunked(q, k, v, causal=causal, window=window, chunk=cfg.attn_chunk)
    return attend(q, k, v, causal=causal, window=window)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_specs(cfg: ModelConfig, stacked: Optional[int] = None) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    lead = (stacked,) if stacked else ()
    lax = ("layers",) if stacked else ()
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "w_gate": Spec(lead + (d, ff), lax + ("embed", "mlp")),
            "w_up": Spec(lead + (d, ff), lax + ("embed", "mlp")),
            "w_down": Spec(lead + (ff, d), lax + ("mlp", "embed")),
        }
    return {
        "w_up": Spec(lead + (d, ff), lax + ("embed", "mlp")),
        "b_up": Spec(lead + (ff,), lax + ("mlp",), "zeros"),
        "w_down": Spec(lead + (ff, d), lax + ("mlp", "embed")),
        "b_down": Spec(lead + (d,), lax + ("embed",), "zeros"),
    }


def apply_mlp(cfg: ModelConfig, p: dict, x):
    if cfg.mlp_type == "swiglu":
        return jnp.einsum(
            "bsf,fd->bsd",
            jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["w_gate"]))
            * jnp.einsum("bsd,df->bsf", x, p["w_up"]),
            p["w_down"],
        )
    if cfg.mlp_type == "geglu":
        return jnp.einsum(
            "bsf,fd->bsd",
            jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w_gate"]))
            * jnp.einsum("bsd,df->bsf", x, p["w_up"]),
            p["w_down"],
        )
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w_up"]) + p["b_up"])
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"]) + p["b_down"]


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_specs(cfg: ModelConfig) -> dict:
    V, d = cfg.padded_vocab, cfg.d_model
    s = {"tok": Spec((V, d), ("vocab", "embed"), scale=0.02)}
    if not cfg.tied_embeddings:
        s["head"] = Spec((d, V), ("embed", "vocab"))
    return s


def embed(p: dict, tokens):
    return jnp.take(p["tok"], tokens, axis=0)


def unembed(cfg: ModelConfig, p: dict, x):
    if cfg.tied_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, p["tok"])
    return jnp.einsum("bsd,dv->bsv", x, p["head"])


def xent_loss(cfg: ModelConfig, logits, labels):
    """Mean cross-entropy over real-vocab logits (padding masked out)."""
    V = cfg.vocab_size
    logits = logits[..., : cfg.padded_vocab]
    pad = logits.shape[-1] - V
    if pad:
        neg = jnp.full((pad,), -1e30, dtype=logits.dtype)
        logits = logits.at[..., V:].set(neg)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# KV cache helpers (decode shapes lower serve_step against these)
# ---------------------------------------------------------------------------


def kv_cache_specs(cfg: ModelConfig, batch: int, cache_len: int, layers: int) -> dict:
    Kv, hd = cfg.padded_kv_heads, cfg.head_dim_
    eff = cache_len if cfg.sliding_window is None else min(cache_len, cfg.sliding_window)
    return {
        "k": Spec((layers, batch, eff, Kv, hd), ("layers", "batch", "seq", "kv_heads", "head_dim")),
        "v": Spec((layers, batch, eff, Kv, hd), ("layers", "batch", "seq", "kv_heads", "head_dim")),
        "len": Spec((batch,), ("batch",), "zeros", dtype="int32"),
    }


def cache_update(cache_k, cache_v, k_new, v_new, lengths, window: Optional[int] = None, layer=None):
    """Insert one decode step's K/V at position ``lengths`` (ring for SWA).
    With ``layer``, the caches are stacked [layers, B, T, ...] and the step
    is written into that layer's slice, in place."""
    lead = () if layer is None else (layer,)
    T = cache_k.shape[len(lead) + 1]
    if window is not None:
        idx = lengths % T
    else:
        idx = jnp.minimum(lengths, T - 1)
    b = jnp.arange(cache_k.shape[len(lead)])
    cache_k = cache_k.at[lead + (b, idx)].set(k_new[:, 0])
    cache_v = cache_v.at[lead + (b, idx)].set(v_new[:, 0])
    return cache_k, cache_v
