"""Mamba2 (SSD — state-space duality) attention-free LM.

Block: RMSNorm → {z, x, B, C, dt} projections → causal depthwise conv on
(x|B|C) → SSD chunked scan (kernels.ops.ssd) → gated RMSNorm → out proj.
Decode carries (conv tail, SSM state) — O(1) in sequence length, which is
why this arch runs the long_500k shape.  The mixer (all of the block but
its norm and residual) is ``mixer`` over whole sequences and
``mixer_step`` for one token; ``mamba_hybrid`` runs the same two.

Sharding: SSD heads over "model" (64 heads / 16 = 4), projections
column/row-parallel, conv channels over "model".
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops as kops

from .config import ModelConfig
from .layers import embed, embed_specs, norm_spec, rmsnorm, unembed
from .param import Spec
from .transformer import _remat, model_scan


def _dims(cfg: ModelConfig) -> Tuple[int, int, int, int, int]:
    s = cfg.ssm
    di = s.expand * cfg.d_model
    nh = di // s.head_dim
    conv_ch = di + 2 * s.n_groups * s.d_state
    return di, nh, s.n_groups, s.d_state, conv_ch


def specs(cfg: ModelConfig) -> dict:
    assert cfg.ssm is not None
    L, d = cfg.num_layers, cfg.d_model
    di, nh, G, N, conv_ch = _dims(cfg)
    K = cfg.ssm.d_conv
    return {
        "embed": embed_specs(cfg),
        "blocks": {
            "ln": norm_spec(cfg, stacked=L),
            "wz": Spec((L, d, di), ("layers", "embed", "channels")),
            "wx": Spec((L, d, di), ("layers", "embed", "channels")),
            "wB": Spec((L, d, G * N), ("layers", "embed", "state")),
            "wC": Spec((L, d, G * N), ("layers", "embed", "state")),
            "wdt": Spec((L, d, nh), ("layers", "embed", "ssm_heads")),
            "conv_w": Spec((L, K, conv_ch), ("layers", "conv", "channels")),
            **({"conv_b": Spec((L, conv_ch), ("layers", "channels"), "zeros")} if cfg.ssm.conv_bias else {}),
            "A_log": Spec((L, nh), ("layers", "ssm_heads"), "ssm_a"),
            "D": Spec((L, nh), ("layers", "ssm_heads"), "ones"),
            "dt_bias": Spec((L, nh), ("layers", "ssm_heads"), "ssm_dt"),
            "norm_g": Spec((L, di), ("layers", "channels"), "zeros"),
            "wo": Spec((L, di, d), ("layers", "channels", "embed")),
        },
        "ln_f": norm_spec(cfg),
    }


def _mix(cfg: ModelConfig, p: dict, h, conv_state=None):
    """Projections + conv; returns (z, xs, Bm, Cm, dt, new conv tail)."""
    di, nh, G, N, conv_ch = _dims(cfg)
    z = jnp.einsum("bsd,de->bse", h, p["wz"])
    xs = jnp.einsum("bsd,de->bse", h, p["wx"])
    Bm = jnp.einsum("bsd,de->bse", h, p["wB"])
    Cm = jnp.einsum("bsd,de->bse", h, p["wC"])
    dtl = jnp.einsum("bsd,dh->bsh", h, p["wdt"])
    xbc = jnp.concatenate([xs, Bm, Cm], axis=-1)
    xbc, conv_tail = kops.causal_conv1d(xbc, p["conv_w"], state=conv_state)
    if cfg.ssm.conv_bias:
        xbc = xbc + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs, Bm, Cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    dt = jax.nn.softplus(dtl.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    return z, xs, Bm, Cm, dt, conv_tail


def _out(cfg: ModelConfig, p: dict, y, z):
    """Gated RMSNorm and out projection."""
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), p["norm_g"], cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y, p["wo"])


def mixer(cfg: ModelConfig, p: dict, hn, mesh=None):
    """The mixer over whole sequences (SSD chunked scan): normed input →
    (out, conv tail, SSM state)."""
    di, nh, G, N, _ = _dims(cfg)
    B, S, _ = hn.shape
    z, xs, Bm, Cm, dt, conv_tail = _mix(cfg, p, hn)
    y, st = kops.ssd(
        xs.reshape(B, S, nh, cfg.ssm.head_dim),
        dt,
        p["A_log"],
        Bm.reshape(B, S, G, N),
        Cm.reshape(B, S, G, N),
        p["D"],
        chunk=min(cfg.ssm.chunk, S),
        mesh=mesh,
    )
    return _out(cfg, p, y.reshape(B, S, di), z), conv_tail, st


def mixer_step(cfg: ModelConfig, p: dict, hn, conv_state, state, l):
    """The mixer for one token: normed input [B, 1, d], the layer's conv
    state, the stacked SSM state and the layer's index in it → (out, conv
    tail, state), layer ``l`` of the state updated in place."""
    di, nh, G, N, _ = _dims(cfg)
    B = hn.shape[0]
    z, xs, Bm, Cm, dt, conv_tail = _mix(cfg, p, hn, conv_state=conv_state)
    y, state = kops.ssd_step_inplace(
        state,
        l,
        xs[:, 0].reshape(B, nh, cfg.ssm.head_dim),
        dt[:, 0],
        p["A_log"],
        Bm[:, 0].reshape(B, G, N),
        Cm[:, 0].reshape(B, G, N),
        p["D"],
    )
    return _out(cfg, p, y.reshape(B, 1, di), z), conv_tail, state


def block(cfg: ModelConfig, p: dict, x, mesh=None):
    out, _, _ = mixer(cfg, p, rmsnorm(x, p["ln"]["w"], cfg.norm_eps), mesh)
    return x + out


def forward_train(cfg: ModelConfig, params: dict, batch: dict, mesh=None):
    x = embed(params["embed"], batch["tokens"])
    body = _remat(cfg, lambda h, pl: (block(cfg, pl, h, mesh), None))
    x, _ = model_scan(cfg, body, x, params["blocks"])
    x = rmsnorm(x, params["ln_f"]["w"], cfg.norm_eps)
    return unembed(cfg, params["embed"], x)


# ---------------------------------------------------------------------------
# Serving: recurrent state instead of a KV cache
# ---------------------------------------------------------------------------


def cache_specs(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    """State is O(1) in cache_len — the whole point of the SSM family."""
    L = cfg.num_layers
    di, nh, G, N, conv_ch = _dims(cfg)
    K = cfg.ssm.d_conv
    return {
        "conv": Spec((L, batch, K - 1, conv_ch), ("layers", "batch", None, "channels"), "zeros"),
        "state": Spec(
            (L, batch, nh, cfg.ssm.head_dim, N),
            ("layers", "batch", "ssm_heads", None, None),
            "zeros",
        ),
        "len": Spec((batch,), ("batch",), "zeros", dtype="int32"),
    }


def prefill(cfg: ModelConfig, params: dict, batch: dict, cache_len: int, mesh=None):
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed(params["embed"], tokens)

    def body(h, pl):
        out, conv_tail, st = mixer(cfg, pl, rmsnorm(h, pl["ln"]["w"], cfg.norm_eps), mesh)
        return h + out, (conv_tail, st.astype(x.dtype))

    x, (convs, states) = model_scan(cfg, _remat(cfg, body), x, params["blocks"])
    x = rmsnorm(x, params["ln_f"]["w"], cfg.norm_eps)
    logits = unembed(cfg, params["embed"], x[:, -1:])
    cache = {"conv": convs, "state": states, "len": jnp.full((B,), S, jnp.int32)}
    return logits, cache


def decode_step(cfg: ModelConfig, params: dict, cache: dict, batch: dict):
    x = embed(params["embed"], batch["token"][:, None])

    # The cache leaves are loop-carried and updated one layer slice at a time,
    # in place in the donated cache (stacked scan outputs would be a fresh
    # [L, ...] buffer that XLA copies into the cache after the loop).  Each
    # layer's conv tail is written at the top of the next iteration, the last
    # one after the loop: the old tail is read by several fused ops, and XLA
    # copies the whole buffer unless all of them precede its update.
    def body(carry, pl):
        h, conv, state, tail, l = carry
        conv = lax.dynamic_update_index_in_dim(conv, tail, jnp.maximum(l - 1, 0), 0)
        conv_st = lax.dynamic_index_in_dim(conv, l, 0, keepdims=False)
        hn = rmsnorm(h, pl["ln"]["w"], cfg.norm_eps)
        out, conv_tail, state = mixer_step(cfg, pl, hn, conv_st, state, l)
        return (h + out, conv, state, conv_tail.astype(conv.dtype), l + 1), None

    conv = cache["conv"]
    init = (x, conv, cache["state"], conv[0], jnp.int32(0))
    (x, conv, state, tail, _), _ = model_scan(cfg, body, init, params["blocks"])
    conv = lax.dynamic_update_index_in_dim(conv, tail, conv.shape[0] - 1, 0)
    x = rmsnorm(x, params["ln_f"]["w"], cfg.norm_eps)
    logits = unembed(cfg, params["embed"], x)
    return logits, {"conv": conv, "state": state, "len": cache["len"] + 1}
