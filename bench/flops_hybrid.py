"""Operations and bytes of the Granite-4.0-H model (family ``mamba_hybrid``)
and of its flash attention calls, from shapes.

As in ``bench/flops.py``, these count what the algorithm needs: the routed
experts at the pairs the router sends to the held experts (``k`` of ``E``
experts per token, ``len(held)`` of them here: the expected pairs), not
every held expert on every token; causal attention at half its square.
``z`` is ``bench.weights_hybrid.dims`` of a configuration.
"""

from __future__ import annotations

from bench import flops


def mamba_flops_per_token(z: dict) -> int:
    """One Mamba2 mixer per token without its scan: five input projections,
    the depthwise conv, the out projection (``bench.flops``'s block)."""
    return flops.block_flops_per_token(z)


def attn_proj_flops_per_token(z: dict) -> int:
    """q, k, v and out projections of one attention mixer, per token."""
    return 2 * z["d"] * (2 * z["Hq"] * z["hd"] + 2 * z["Kv"] * z["hd"])


def moe_flops_per_token(z: dict) -> int:
    """Router, the expected routed pairs on held experts, the shared expert."""
    expert = 6 * z["d"] * z["f"]
    return 2 * z["d"] * z["E"] + z["k"] * len(z["held"]) * expert // z["E"] + 6 * z["d"] * z["fs"]


def flash_flops(S: int, z: dict) -> int:
    """One causal GQA attention over S tokens: q·kᵀ and p·v over the lower
    triangle, 2·S²·H·hd."""
    return 2 * S * S * z["Hq"] * z["hd"]


def flash_bytes(S: int, z: dict, act_bytes: int = 2) -> int:
    """Least HBM traffic of one call: q, k, v read and o written."""
    return (2 * S * z["Hq"] * z["hd"] + 2 * S * z["Kv"] * z["hd"]) * act_bytes


def flash_min_time(S: int, z: dict, flops_peak: float, bw_peak: float) -> float:
    """Least seconds on the chip for one flash attention call."""
    return max(flash_flops(S, z) / flops_peak, flash_bytes(S, z) / bw_peak)


def prefill_flops(S: int, z: dict) -> int:
    """One prompt of S tokens: every layer over S tokens (SSD chunked,
    attention causal) and the head at the last position."""
    ssd = flops.ssd_flops(S, z["H"], z["P"], z["G"], z["N"], min(z["chunk"], S))
    per_token = (z["Lm"] * mamba_flops_per_token(z) + z["La"] * attn_proj_flops_per_token(z)
                 + z["L"] * moe_flops_per_token(z))
    return S * per_token + z["Lm"] * ssd + z["La"] * flash_flops(S, z) + flops.head_flops(z)


def decode_flops_per_token(z: dict, context: int) -> int:
    """One token at ``context`` tokens of cache: the Mamba2 recurrent step
    (6·H·P·N), attention over the cache (4·context·H·hd), the MoE, the head."""
    step = 6 * z["H"] * z["P"] * z["N"]
    attn = attn_proj_flops_per_token(z) + 4 * context * z["Hq"] * z["hd"]
    return (z["Lm"] * (mamba_flops_per_token(z) + step) + z["La"] * attn
            + z["L"] * moe_flops_per_token(z) + flops.head_flops(z))
