"""Mixture-of-Experts LM (moonshot 64e/top-6, kimi-k2 384e/top-8).

Expert parallelism is explicit (shard_map + lax.all_to_all over the "model"
axis) rather than GSPMD-inferred, so the collective schedule is transparent
— the dispatch/combine all_to_alls are exactly the bytes the roofline's
collective term counts, and the §Perf hillclimb can attack them directly
(capacity factor, int8 dispatch compression).

Two dispatch paths:
  * ``_moe_ep_seq``     — train/prefill: tokens sequence-sharded over the
    model axis; sort-based grouping; a2a to expert shards; grouped GEMMs;
    a2a back; weighted combine. DeepSeek-EP style, adapted to TPU/JAX.
  * ``_moe_ep_replicated`` — decode (seq=1): tokens replicated over the
    model axis; each shard computes only its local experts' contribution;
    psum combine. No a2a on the latency-critical decode path.
Fallback ``_moe_dense`` (all experts, masked combine) is the oracle for
tests and the single-device smoke path.

``held_moe`` is one device's share of an expert-parallel layer whose other
experts live on other devices: it routes over all experts and computes the
part of the result that its held experts give, with no capacity and so no
drops (``mamba_hybrid``).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.jaxcompat import shard_map as _shard_map

from .config import ModelConfig
from .layers import (
    apply_norm,
    attend,
    attend_cfg,
    attn_out,
    attn_specs,
    cache_update,
    embed,
    embed_specs,
    kv_cache_specs,
    norm_spec,
    qkv,
    unembed,
)
from .param import Spec
from .transformer import _remat, model_scan


def specs(cfg: ModelConfig) -> dict:
    assert cfg.moe is not None
    L, d, E, ffe = cfg.num_layers, cfg.d_model, cfg.moe.num_experts, cfg.moe.d_ff_expert
    return {
        "embed": embed_specs(cfg),
        "blocks": {
            "attn": attn_specs(cfg, stacked=L),
            "router": Spec((L, d, E), ("layers", "embed", None)),  # replicated: global top-k
            "w_gate": Spec((L, E, d, ffe), ("layers", "experts", "embed", "expert_mlp")),
            "w_up": Spec((L, E, d, ffe), ("layers", "experts", "embed", "expert_mlp")),
            "w_down": Spec((L, E, ffe, d), ("layers", "experts", "expert_mlp", "embed")),
            "ln1": norm_spec(cfg, stacked=L),
            "ln2": norm_spec(cfg, stacked=L),
        },
        "ln_f": norm_spec(cfg),
    }


# ---------------------------------------------------------------------------
# Sort-based token grouping (static shapes; overflow drops, standard capacity)
# ---------------------------------------------------------------------------


def group_tokens(xt, eid, tok, n_groups: int, capacity: int):
    """Group assignment rows into a [n_groups, capacity, d] buffer.

    eid may contain the sentinel ``n_groups`` for invalid assignments (they
    sort last and scatter out-of-bounds → dropped).  Returns (buffer,
    eid_sorted, pos, order) — the metadata needed to ungroup results.
    """
    A = eid.shape[0]
    order = jnp.argsort(eid)  # stable
    eid_s = eid[order]
    tok_s = tok[order]
    seg_start = jnp.searchsorted(eid_s, jnp.arange(n_groups))
    pos = jnp.arange(A) - seg_start[jnp.clip(eid_s, 0, n_groups - 1)]
    buf = jnp.zeros((n_groups, capacity, xt.shape[-1]), xt.dtype)
    buf = buf.at[eid_s, pos].add(xt[tok_s])  # OOB (sentinel / pos>=cap) dropped
    return buf, eid_s, pos, order, tok_s


def ungroup_tokens(y, eid_s, pos, n_tokens: int, tok_s, weights_s):
    """Inverse of group_tokens + weighted combine into [n_tokens, d]."""
    ya = y.at[eid_s, pos].get(mode="fill", fill_value=0)  # [A, d]
    out = jnp.zeros((n_tokens, y.shape[-1]), y.dtype)
    return out.at[tok_s].add(ya * weights_s[:, None])


def expert_ffn(buf, w_gate, w_up, w_down):
    """Grouped GEMMs: [E, C, d] × [E, d, f] — the MXU-friendly MoE core."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_up
    )
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _router(cfg: ModelConfig, wr, xt):
    """Returns (weights [T,k], expert ids [T,k], aux load-balance loss)."""
    E, k = cfg.moe.num_experts, cfg.moe.top_k
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), wr.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)  # renormalize top-k
    # switch-style aux loss: E * Σ_e (fraction dispatched) * (mean prob)
    f = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0
    )
    p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * p)
    return vals.astype(xt.dtype), idx, aux


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# Dispatch paths
# ---------------------------------------------------------------------------


def _moe_dense(cfg: ModelConfig, p: dict, xt):
    """Oracle: every expert on every token, masked combine. O(T·E·d·f)."""
    E = cfg.moe.num_experts
    w, idx, aux = _router(cfg, p["router"], xt)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, p["w_gate"])) * jnp.einsum(
        "td,edf->tef", xt, p["w_up"]
    )
    y_all = jnp.einsum("tef,efd->ted", h, p["w_down"])  # [T, E, d]
    combine = (
        jnp.zeros((xt.shape[0], E), xt.dtype)
        .at[jnp.arange(xt.shape[0])[:, None], idx]
        .add(w)
    )
    return jnp.einsum("ted,te->td", y_all, combine), aux


def _held_index(cfg: ModelConfig) -> np.ndarray:
    """Position of each expert id among the held ones; ``len(held)`` for an
    expert held elsewhere."""
    held = cfg.moe.held
    out = np.full((cfg.moe.num_experts,), len(held), np.int32)
    out[np.asarray(held, np.int64)] = np.arange(len(held), dtype=np.int32)
    return out


def swiglu(x, w_gate, w_up, w_down):
    """One SwiGLU expert on tokens x [T, d]."""
    return jnp.dot(jax.nn.silu(jnp.dot(x, w_gate)) * jnp.dot(x, w_up), w_down)


def held_moe(cfg: ModelConfig, p: dict, xt, grouped: bool):
    """The held experts' part of the MoE on tokens xt [T, d], plus the shared
    expert where the layer has one.

    The router chooses ``top_k`` of all ``num_experts`` with the softmax over
    the chosen logits; only pairs whose expert is held are computed, every
    one of them.  ``grouped``: the pairs sorted by expert and run as one
    grouped matmul (``lax.ragged_dot``), each expert on its own tokens; else
    every held expert on every token, weighted by its gate (0 where not
    chosen), which at a decode batch costs no more than reading the weights.
    ``p``: ``router`` [d, E], ``w_gate``/``w_up`` [Eh, d, f], ``w_down``
    [Eh, f, d], ``shared_*``.  Returns (out [T, d], pairs per held expert [Eh]).
    """
    T = xt.shape[0]
    Eh = len(cfg.moe.held)
    w, idx, _ = _router(cfg, p["router"], xt)
    local = jnp.asarray(_held_index(cfg))[idx]  # [T, k]; Eh: held elsewhere
    load = jnp.bincount(local.reshape(-1), length=Eh + 1)[:Eh]
    if grouped:
        k = idx.shape[1]
        order = jnp.argsort(local.reshape(-1))  # held pairs first, by expert
        xs = xt[order // k]
        h = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], load)) * jax.lax.ragged_dot(
            xs, p["w_up"], load
        )
        y = jax.lax.ragged_dot(h, p["w_down"], load)  # rows past the groups: unused
        y = y[jnp.argsort(order)].reshape(T, k, -1)  # back to (token, choice)
        y = jnp.where((local < Eh)[..., None], y.astype(jnp.float32) * w[..., None], 0.0)
        out = jnp.sum(y, axis=1)
    else:
        gate = jnp.zeros((T, Eh + 1), w.dtype).at[jnp.arange(T)[:, None], local].add(w)[:, :Eh]
        h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, p["w_gate"])) * jnp.einsum(
            "td,edf->tef", xt, p["w_up"]
        )
        out = jnp.einsum("tef,efd->td", h * gate[:, :, None], p["w_down"],
                         preferred_element_type=jnp.float32)
    if cfg.moe.d_ff_shared:
        out = out + swiglu(xt, p["shared_gate"], p["shared_up"], p["shared_down"])
    return out.astype(xt.dtype), load


def _local_expert_compute(cfg, p_local, xt, w, idx, ep: int, my_shard, capacity: int):
    """Group tokens routed to *this shard's* experts, run them, combine."""
    E = cfg.moe.num_experts
    E_loc = E // ep
    T, k = idx.shape
    a_eid = idx.reshape(-1)  # global expert ids, [T*k]
    a_tok = jnp.repeat(jnp.arange(T), k)
    a_w = w.reshape(-1)
    mine = (a_eid // E_loc) == my_shard
    loc_eid = jnp.where(mine, a_eid % E_loc, E_loc)  # sentinel E_loc
    buf, eid_s, pos, order, tok_s = group_tokens(xt, loc_eid, a_tok, E_loc, capacity)
    y = expert_ffn(buf, p_local["w_gate"], p_local["w_up"], p_local["w_down"])
    w_s = jnp.where(mine, a_w, 0.0)[order]
    return ungroup_tokens(y, eid_s, pos, T, tok_s, w_s)


def _moe_ep_replicated(cfg: ModelConfig, p: dict, x, mesh: Mesh, dp_axes):
    """Decode path: x replicated over 'model'; local experts + psum combine."""
    B, S, d = x.shape
    ep = mesh.shape["model"]
    cf = cfg.moe.capacity_factor

    def inner(pr, pg, pu, pd, xl):
        Bl = xl.shape[0]
        T = Bl * S
        xt = xl.reshape(T, d)
        wr, idx, aux = _router(cfg, pr, xt)
        my = jax.lax.axis_index("model")
        cap = max(int(np.ceil(T * cfg.moe.top_k * cf / ep)), 4)
        out = _local_expert_compute(
            cfg, {"w_gate": pg, "w_up": pu, "w_down": pd}, xt, wr, idx, ep, my, cap
        )
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))  # replicate for out_spec P()
        return out.reshape(Bl, S, d), aux

    fn = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(), P("model"), P("model"), P("model"), P(dp_axes)),
        out_specs=(P(dp_axes), P()),
        check_vma=False,
    )
    return fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)


def _moe_ep_2d(cfg: ModelConfig, p: dict, x, mesh: Mesh, dp_axes):
    """Resident 2D expert sharding for decode (§Perf, kimi-k2 hillclimb).

    Weights: experts over "model" × expert-FFN dim over the data axes —
    nothing is re-gathered per step.  Tokens are all_gather'ed over the data
    axes (MBs), each (data, model) shard computes its expert slice's partial
    FFN (column/row parallel over expert_mlp), and a single psum over the
    whole mesh combines expert contributions (model) and FFN partials (data)
    at once.  Collective bytes per layer scale with activations, not weights.
    """
    B, S, d = x.shape
    ep = mesh.shape["model"]
    E_loc = cfg.moe.num_experts // ep
    cf = cfg.moe.capacity_factor

    def inner(pr, pg, pu, pd, xl):
        # gather the (tiny) decode activations over the data axes
        xg = xl
        for a in dp_axes:
            xg = jax.lax.all_gather(xg, a, axis=0, tiled=True)
        T = xg.shape[0] * xg.shape[1]
        xt = xg.reshape(T, d)
        wr, idx, aux = _router(cfg, pr, xt)
        my = jax.lax.axis_index("model")
        cap = max(int(np.ceil(T * cfg.moe.top_k * cf / ep)), 4)
        # grouping identical to the replicated path, but the FFN runs on
        # expert_mlp-sharded weights -> results are partial over "data"
        a_eid = idx.reshape(-1)
        a_tok = jnp.repeat(jnp.arange(T), cfg.moe.top_k)
        a_w = wr.reshape(-1)
        mine = (a_eid // E_loc) == my
        loc_eid = jnp.where(mine, a_eid % E_loc, E_loc)
        buf, eid_s, pos, order, tok_s = group_tokens(xt, loc_eid, a_tok, E_loc, cap)
        y = expert_ffn(buf, pg, pu, pd)
        w_s = jnp.where(mine, a_w, 0.0)[order]
        out_all = ungroup_tokens(y, eid_s, pos, T, tok_s, w_s)
        out_all = jax.lax.psum(out_all, ("model",) + tuple(dp_axes))
        # slice this shard's batch rows back out
        rows = xl.shape[0] * S
        flat_idx = jnp.zeros((), jnp.int32)
        for a in dp_axes:
            flat_idx = flat_idx * mesh.shape[a] + jax.lax.axis_index(a)
        out = jax.lax.dynamic_slice_in_dim(out_all, flat_idx * rows, rows, axis=0)
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
        return out.reshape(xl.shape[0], S, d), aux

    fn = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            P(),
            P("model", None, dp_axes),
            P("model", None, dp_axes),
            P("model", dp_axes, None),
            P(dp_axes),
        ),
        out_specs=(P(dp_axes), P()),
        check_vma=False,
    )
    return fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)


def _moe_ep_seq(cfg: ModelConfig, p: dict, x, mesh: Mesh, dp_axes):
    """Train/prefill path: sequence-sharded dispatch with all_to_all."""
    B, S, d = x.shape
    ep = mesh.shape["model"]
    E = cfg.moe.num_experts
    E_loc = E // ep
    k = cfg.moe.top_k
    cf = cfg.moe.capacity_factor

    def inner(pr, pg, pu, pd, xl):
        Bl, Sl = xl.shape[0], xl.shape[1]
        T = Bl * Sl  # tokens on this shard
        xt = xl.reshape(T, d)
        wr, idx, aux = _router(cfg, pr, xt)
        # --- send-side grouping by destination shard --------------------
        a_eid = idx.reshape(-1)
        a_tok = jnp.repeat(jnp.arange(T), k)
        a_w = wr.reshape(-1)
        dst = a_eid // E_loc  # [T*k] destination shard
        cap_send = _round_up(max(int(np.ceil(T * k * cf / ep)), 4), 4)
        buf, dst_s, pos, order, tok_s = group_tokens(xt, dst, a_tok, ep, cap_send)
        # payload: local expert id per slot (sentinel E_loc marks empty)
        eid_payload = jnp.full((ep, cap_send), E_loc, jnp.int32)
        eid_payload = eid_payload.at[dst_s, pos].set((a_eid % E_loc)[order].astype(jnp.int32))
        # --- dispatch a2a ------------------------------------------------
        recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0)
        recv_eid = jax.lax.all_to_all(eid_payload, "model", split_axis=0, concat_axis=0)
        R = ep * cap_send
        rt = recv.reshape(R, d)
        re = recv_eid.reshape(R)
        # --- local expert grouping + FFN ---------------------------------
        cap_e = _round_up(max(int(np.ceil(R * cf / E_loc)), 4), 4)
        gbuf, eid_s2, pos2, order2, tok_s2 = group_tokens(rt, re, jnp.arange(R), E_loc, cap_e)
        y = expert_ffn(gbuf, pg, pu, pd)
        yr = jnp.zeros((R, d), x.dtype)
        ya = y.at[eid_s2, pos2].get(mode="fill", fill_value=0)
        yr = yr.at[tok_s2].add(jnp.where((eid_s2 < E_loc)[:, None], ya, 0))
        # --- return a2a + source-side combine -----------------------------
        back = jax.lax.all_to_all(yr.reshape(ep, cap_send, d), "model", split_axis=0, concat_axis=0)
        out = ungroup_tokens(back, dst_s, pos, T, tok_s, a_w[order])
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))  # replicate for out_spec P()
        return out.reshape(Bl, Sl, d), aux

    fn = _shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(), P("model"), P("model"), P("model"), P(dp_axes, "model")),
        out_specs=(P(dp_axes, "model"), P()),
        check_vma=False,
    )
    return fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)


def moe_ffn(cfg: ModelConfig, p: dict, x, mesh: Optional[Mesh]):
    """Dispatch to the right path for (mesh, sequence length)."""
    if mesh is None or "model" not in mesh.shape or mesh.shape["model"] == 1:
        B, S, d = x.shape
        out, aux = _moe_dense(cfg, p, x.reshape(-1, d))
        return out.reshape(B, S, d), aux
    ep = mesh.shape["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if cfg.moe.num_experts % ep != 0:
        raise ValueError(f"{cfg.moe.num_experts} experts not divisible by ep={ep}")
    if x.shape[1] % ep == 0 and x.shape[1] >= ep:
        return _moe_ep_seq(cfg, p, x, mesh, dp_axes)
    if cfg.serve_2d:
        return _moe_ep_2d(cfg, p, x, mesh, dp_axes)
    return _moe_ep_replicated(cfg, p, x, mesh, dp_axes)


# ---------------------------------------------------------------------------
# Full model (mirrors transformer.py with MoE FFN + aux loss accumulation)
# ---------------------------------------------------------------------------


def _block_parts(p: dict) -> Tuple[dict, dict]:
    moe_keys = ("router", "w_gate", "w_up", "w_down")
    return (
        {k: v for k, v in p.items() if k not in moe_keys},
        {k: p[k] for k in moe_keys},
    )


def block(cfg: ModelConfig, p: dict, x, positions, mesh):
    base, moe_p = _block_parts(p)
    h = apply_norm(cfg, base["ln1"], x)
    q, k, v = qkv(cfg, base["attn"], h, positions)
    ctx = attend_cfg(cfg, q, k, v, causal=True, window=cfg.sliding_window)
    x = x + attn_out(base["attn"], ctx)
    h = apply_norm(cfg, base["ln2"], x)
    y, aux = moe_ffn(cfg, moe_p, h, mesh)
    return x + y, aux


def forward_train(cfg: ModelConfig, params: dict, batch: dict, mesh=None):
    tokens = batch["tokens"]
    x = embed(params["embed"], tokens)
    positions = jnp.arange(x.shape[1])[None, :]

    def body(carry, pl):
        h, aux = carry
        h, a = block(cfg, pl, h, positions, mesh)
        return (h, aux + a), None

    (x, aux), _ = model_scan(cfg, _remat(cfg, body), (x, jnp.zeros((), jnp.float32)), params["blocks"])
    x = apply_norm(cfg, params["ln_f"], x)
    return unembed(cfg, params["embed"], x), aux / cfg.num_layers


def cache_specs(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    return kv_cache_specs(cfg, batch, cache_len, cfg.num_layers)


def prefill(cfg: ModelConfig, params: dict, batch: dict, cache_len: int, mesh=None):
    tokens = batch["tokens"]
    x = embed(params["embed"], tokens)
    B, S = x.shape[0], x.shape[1]
    eff = cache_len
    positions = jnp.arange(S)[None, :]

    def body(carry, pl):
        h, aux = carry
        base, moe_p = _block_parts(pl)
        hn = apply_norm(cfg, base["ln1"], h)
        q, k, v = qkv(cfg, base["attn"], hn, positions)
        ctx = attend_cfg(cfg, q, k, v, causal=True)
        h = h + attn_out(base["attn"], ctx)
        hn = apply_norm(cfg, base["ln2"], h)
        y, a = moe_ffn(cfg, moe_p, hn, mesh)
        h = h + y
        if S >= eff:
            kk, vv = k[:, -eff:], v[:, -eff:]
        else:
            pad = [(0, 0), (0, eff - S), (0, 0), (0, 0)]
            kk, vv = jnp.pad(k, pad), jnp.pad(v, pad)
        return (h, aux + a), (kk, vv)

    (x, aux), (ks, vs) = model_scan(
        cfg, _remat(cfg, body), (x, jnp.zeros((), jnp.float32)), params["blocks"]
    )
    x = apply_norm(cfg, params["ln_f"], x)
    logits = unembed(cfg, params["embed"], x[:, -1:])
    return logits, {"k": ks, "v": vs, "len": jnp.full((B,), S, jnp.int32)}


def decode_step(cfg: ModelConfig, params: dict, cache: dict, batch: dict, mesh=None):
    token = batch["token"]
    lengths = cache["len"]
    x = embed(params["embed"], token[:, None])
    positions = lengths[:, None]

    def body(carry, inputs):
        h = carry
        pl, ck, cv = inputs
        base, moe_p = _block_parts(pl)
        hn = apply_norm(cfg, base["ln1"], h)
        q, k, v = qkv(cfg, base["attn"], hn, positions)
        ck, cv = cache_update(ck, cv, k, v, lengths)
        kv_valid = jnp.minimum(lengths + 1, ck.shape[1])
        ctx = attend(q, ck, cv, causal=False, kv_len=kv_valid)
        h = h + attn_out(base["attn"], ctx)
        hn = apply_norm(cfg, base["ln2"], h)
        y, _ = moe_ffn(cfg, moe_p, hn, mesh)
        return h + y, (ck, cv)

    x, (ks, vs) = model_scan(cfg, body, x, (params["blocks"], cache["k"], cache["v"]))
    x = apply_norm(cfg, params["ln_f"], x)
    logits = unembed(cfg, params["embed"], x)
    return logits, {"k": ks, "v": vs, "len": lengths + 1}
