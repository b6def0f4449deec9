"""Granite-4.0-H (HF ``GraniteMoeHybrid``): Mamba2 and NoPE GQA mixers by
``layer_types``, and in every layer an MoE of routed experts beside a
shared SwiGLU expert.

    x = m_emb · E[tok]
    each layer ℓ:  x += r · mixer_ℓ(rms(x));  x += r · (Σ_top-k g_e SwiGLU_e + SwiGLU_shared)(rms(x))
    logits = rms(x) · Eᵀ / logits_scaling

with r the residual multiplier.  The Mamba2 mixer is ``ssm``'s, with a
conv bias: ``ssm.mixer`` over whole sequences (the SSD kernel) and
``ssm.mixer_step`` in decode (the state updated in place); this module
keeps only their residual, its loops and the conv tails' write order.  The
attention mixer is GQA with no position embedding and scores scaled by
``attention_multiplier``, the Pallas flash kernel in prefill and the KV
cache in decode.  The MoE computes the held experts'
part only (``moe.held_moe``): one device's share of an expert-parallel layer.

Parameters: ``mamba`` holds the ssm block's leaves stacked over the Mamba2
layers, ``attn`` the attention's over the attention layers, ``moe`` the
feed-forward's over all layers.  The layers run in runs of one kind
(granite's first ten: 5 Mamba2, 1 attention, 4 Mamba2), each run a scan over
its layers that reads their weights and writes their cache slices by index,
so that decode updates the donated cache in place.  (A run of one Mamba2
layer is a loop of one trip, which XLA inlines; the CPU backend then keeps
a whole-buffer copy of the conv tails.  Granite's runs are 4 to 9 long.)

Serving state: the conv tails and SSM states of the Mamba2 layers, the K/V
cache of the attention layers, the lengths.  ``prefill`` and
``decode_step`` return, beside logits and cache, the route counts of the
step: [token–expert pairs on held experts, Σ over layers of the largest
held expert's pairs, experts held].
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops as kops

from . import moe, ssm
from .config import ModelConfig
from .layers import (
    attend,
    attn_out,
    attn_specs,
    cache_update,
    embed,
    embed_specs,
    kv_cache_specs,
    norm_spec,
    qkv,
    rmsnorm,
    unembed,
)
from .param import Spec
from .transformer import _remat, model_scan


def layer_types(cfg: ModelConfig) -> Tuple[str, ...]:
    return tuple(cfg.layer_types[: cfg.num_layers])


def runs(cfg: ModelConfig) -> List[Tuple[str, int, int, int]]:
    """(kind, first layer, first index in the kind's stack, layers) of each
    run of consecutive layers of one kind."""
    out: list = []
    seen = {"mamba": 0, "attention": 0}
    for layer, kind in enumerate(layer_types(cfg)):
        if out and out[-1][0] == kind:
            k, first, start, n = out[-1]
            out[-1] = (k, first, start, n + 1)
        else:
            out.append((kind, layer, seen[kind], 1))
        seen[kind] += 1
    return out


def _count(cfg: ModelConfig, kind: str) -> int:
    return sum(t == kind for t in layer_types(cfg))


def specs(cfg: ModelConfig) -> dict:
    assert cfg.moe is not None and cfg.ssm is not None
    L, d, m = cfg.num_layers, cfg.d_model, cfg.moe
    Eh, f, fs = len(m.held), m.d_ff_expert, m.d_ff_shared
    n_m, n_a = _count(cfg, "mamba"), _count(cfg, "attention")
    out = {"embed": embed_specs(cfg), "ln_f": norm_spec(cfg)}
    if n_m:
        out["mamba"] = ssm.specs(dataclasses.replace(cfg, num_layers=n_m))["blocks"]
    if n_a:
        out["attn"] = {"ln": norm_spec(cfg, stacked=n_a), **attn_specs(cfg, stacked=n_a)}
    out["moe"] = {
        "ln": norm_spec(cfg, stacked=L),
        "router": Spec((L, d, m.num_experts), ("layers", "embed", None)),
        "w_gate": Spec((L, Eh, d, f), ("layers", "experts", "embed", "expert_mlp")),
        "w_up": Spec((L, Eh, d, f), ("layers", "experts", "embed", "expert_mlp")),
        "w_down": Spec((L, Eh, f, d), ("layers", "experts", "expert_mlp", "embed")),
    }
    if fs:
        out["moe"].update(
            shared_gate=Spec((L, d, fs), ("layers", "embed", "mlp")),
            shared_up=Spec((L, d, fs), ("layers", "embed", "mlp")),
            shared_down=Spec((L, fs, d), ("layers", "mlp", "embed")),
        )
    return out


def cache_specs(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    out = {}
    n_m, n_a = _count(cfg, "mamba"), _count(cfg, "attention")
    if n_m:
        s = ssm.cache_specs(dataclasses.replace(cfg, num_layers=n_m), batch, cache_len)
        out.update(conv=s["conv"], state=s["state"])
    out.update(kv_cache_specs(cfg, batch, cache_len, n_a) if n_a else {})
    out["len"] = Spec((batch,), ("batch",), "zeros", dtype="int32")
    return out


# ---------------------------------------------------------------------------
# Sub-layers
# ---------------------------------------------------------------------------


def _at(tree, i):
    """Layer ``i`` of a stacked parameter tree (``i`` traced)."""
    return jax.tree_util.tree_map(lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def _residual(cfg: ModelConfig, x, y):
    return x + (y * cfg.residual_multiplier).astype(x.dtype)


def _ffn(cfg: ModelConfig, p: dict, x, grouped: bool, route):
    """The MoE sub-layer with its residual; adds this layer's pairs and
    largest held-expert load to ``route``."""
    B, S, d = x.shape
    h = rmsnorm(x, p["ln"]["w"], cfg.norm_eps)
    y, load = moe.held_moe(cfg, p, h.reshape(B * S, d), grouped)
    return _residual(cfg, x, y.reshape(B, S, d)), route + jnp.stack([jnp.sum(load), jnp.max(load)])


def _route(cfg: ModelConfig, route):
    return jnp.concatenate([route, jnp.asarray([len(cfg.moe.held)], jnp.int32)])


def _embed(cfg: ModelConfig, params: dict, tokens):
    return embed(params["embed"], tokens) * cfg.embedding_multiplier


def _logits(cfg: ModelConfig, params: dict, x):
    x = rmsnorm(x, params["ln_f"]["w"], cfg.norm_eps)
    return unembed(cfg, params["embed"], x) / cfg.logits_scaling


# ---------------------------------------------------------------------------
# Whole sequences: training and prefill
# ---------------------------------------------------------------------------


def _forward(cfg: ModelConfig, params: dict, tokens, cache_len: int, mesh=None):
    """All layers over whole sequences; returns (hidden states, the cache
    a decode continues from, route counts)."""
    B, S = tokens.shape
    x = _embed(cfg, params, tokens)
    route = jnp.zeros((2,), jnp.int32)
    parts: dict = {"conv": [], "state": [], "k": [], "v": []}
    for kind, first, start, n in runs(cfg):

        def body(carry, i, kind=kind, first=first, start=start):
            h, route = carry
            if kind == "mamba":
                p = _at(params["mamba"], start + i)
                y, tail, st = ssm.mixer(cfg, p, rmsnorm(h, p["ln"]["w"], cfg.norm_eps), mesh)
                kept = {"conv": tail, "state": st.astype(h.dtype)}
            else:
                p = _at(params["attn"], start + i)
                hn = rmsnorm(h, p["ln"]["w"], cfg.norm_eps)
                q, k, v = qkv(cfg, p, hn)
                ctx = kops.flash_attention(q, k, v, causal=True, scale=cfg.attention_multiplier)
                y = attn_out(p, ctx)
                if S >= cache_len:
                    k, v = k[:, -cache_len:], v[:, -cache_len:]
                else:
                    pad = [(0, 0), (0, cache_len - S), (0, 0), (0, 0)]
                    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
                kept = {"k": k, "v": v}
            h = _residual(cfg, h, y)
            h, route = _ffn(cfg, _at(params["moe"], first + i), h, True, route)
            return (h, route), kept

        (x, route), kept = model_scan(cfg, _remat(cfg, body), (x, route), jnp.arange(n))
        for key, val in kept.items():
            parts[key].append(val)
    cache = {k: jnp.concatenate(v, axis=0) for k, v in parts.items() if v}
    cache["len"] = jnp.full((B,), S, jnp.int32)
    return x, cache, _route(cfg, route)


def forward_train(cfg: ModelConfig, params: dict, batch: dict, mesh=None):
    tokens = batch["tokens"]
    x, _, _ = _forward(cfg, params, tokens, tokens.shape[1], mesh)
    return _logits(cfg, params, x)


def prefill(cfg: ModelConfig, params: dict, batch: dict, cache_len: int, mesh=None):
    """Last-position logits, the cache row and the route counts of a prompt."""
    x, cache, route = _forward(cfg, params, batch["tokens"], cache_len, mesh)
    return _logits(cfg, params, x[:, -1:]), cache, route


# ---------------------------------------------------------------------------
# Decode: one token for every slot, the cache updated in place
# ---------------------------------------------------------------------------


def decode_step(cfg: ModelConfig, params: dict, cache: dict, batch: dict):
    lengths = cache["len"]
    x = _embed(cfg, params, batch["token"][:, None])
    route = jnp.zeros((2,), jnp.int32)
    cache = dict(cache)
    for kind, first, start, n in runs(cfg):
        if kind == "mamba":
            # as ssm.decode_step: ``ssm.mixer_step`` updates the state in place
            # by layer index; each conv tail is written at the top of the next
            # iteration (the last after the run), after every read of the old
            # buffer

            def body(carry, i, first=first, start=start):
                h, conv, state, tail, route = carry
                j = start + i
                conv = lax.dynamic_update_index_in_dim(conv, tail, jnp.maximum(j - 1, start), 0)
                p = _at(params["mamba"], j)
                hn = rmsnorm(h, p["ln"]["w"], cfg.norm_eps)
                conv_st = lax.dynamic_index_in_dim(conv, j, 0, keepdims=False)
                y, tail, state = ssm.mixer_step(cfg, p, hn, conv_st, state, j)
                h = _residual(cfg, h, y)
                h, route = _ffn(cfg, _at(params["moe"], first + i), h, False, route)
                return (h, conv, state, tail.astype(conv.dtype), route), None

            conv = cache["conv"]
            init = (x, conv, cache["state"], conv[start], route)
            (x, conv, state, tail, route), _ = model_scan(cfg, body, init, jnp.arange(n))
            cache["conv"] = lax.dynamic_update_index_in_dim(conv, tail, start + n - 1, 0)
            cache["state"] = state
        else:

            def body(carry, i, first=first, start=start):
                h, ck, cv, route = carry
                j = start + i
                p = _at(params["attn"], j)
                hn = rmsnorm(h, p["ln"]["w"], cfg.norm_eps)
                q, k, v = qkv(cfg, p, hn)
                ck, cv = cache_update(ck, cv, k, v, lengths, layer=j)
                T = ck.shape[2]
                ctx = attend(
                    q, lax.dynamic_index_in_dim(ck, j, 0, keepdims=False),
                    lax.dynamic_index_in_dim(cv, j, 0, keepdims=False),
                    causal=False, kv_len=jnp.minimum(lengths + 1, T), scale=cfg.attention_multiplier,
                )
                h = _residual(cfg, h, attn_out(p, ctx))
                h, route = _ffn(cfg, _at(params["moe"], first + i), h, False, route)
                return (h, ck, cv, route), None

            init = (x, cache["k"], cache["v"], route)
            (x, cache["k"], cache["v"], route), _ = model_scan(cfg, body, init, jnp.arange(n))
    cache["len"] = lengths + 1
    return _logits(cfg, params, x), cache, _route(cfg, route)
