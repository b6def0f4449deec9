"""Seeded Granite-4.0-H weights (family ``mamba_hybrid``), made on the
device in one jitted call, and the sizes and program configuration of a
configuration file.

The layout is the one the served model takes (``{"embed": {"tok"},
"ln_f", "mamba": {...}, "attn": {...}, "moe": {...}}``, layers stacked on
the leading axis of each group, only the held experts); the reference
reads it by these names.

Scales.  The published multipliers shrink every branch: each mixer and
the MoE enter the residual at 0.22, the embedding at ×12 and the logits
at ÷16.  With N(0, 0.02) everywhere three things go wrong: the routed
experts, which a token reaches through about 1.25 held pairs at a gate
near 0.1, add a tenth of what the shared expert adds; near-uniform
attention (scores ≪ 1 at the 1/128 scale) averages its values away; and
the input embedding (×12, tied to the head) points every position's
logits at its own token, so that every precision serves the same tokens.
A broken MoE or attention would then still agree with the reference.  So:

- Mamba2 mixer: as ``bench/weights.py`` (projections 0.02, gains 0.1,
  ``D`` U(0.5, 1.5)) but conv 0.3; conv bias 0.1;
- attention: q and k 0.08, so that scores spread by about 2.2 (a few keys
  carry each query), v 0.04 and out 0.02;
- experts: router 0.05 (logits spread by about 3.2, so that a gate at the
  top-10 boundary is a few hundredths of the first and a near tie there
  changes little); gate and up 0.02; down projections 0.1 (routed) and
  0.02 (shared), so that the routed part and the shared part come out at
  comparable sizes;
- the embedding 0.001: the residual stream is the layers' (the input is
  0.012 RMS), no position's own token leads its logits, and the logits
  (÷16) spread by about σ = 0.004.

These are the scales at the published widths; a matrix's scale goes as
1/√fan-in, so that a configuration of smaller widths (the CPU tests')
keeps the same balance.

Measured in float32 at the published widths, layer 0 (RMS of each branch
before its 0.22): Mamba2 mixer 1.82, routed experts 0.57, shared expert
0.80.  The gap the benchmark compares (how far the reference's logit of
the served token lies below its best) grows with how far a rounding
error is carried through the layers.  On the CPU, the whole stage (10
layers), vocabulary cut to 8192, one row of 256 tokens, largest gap in
units of σ, bf16 program against the fp8 control: with the first
balance (router 0.1, routed down 0.2, shared down 0.04, conv 0.5) 1.92
against 3.98: the steep gates of a large routed part carried a bf16
error up to 66% of the logits' spread at the worst position.  With these
scales 0.15 against 1.68 (0.18 against 2.57 at 1024 tokens, another
seed), and each fault of ``tests/bench/test_hybrid.py`` 4.1 to 6.1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.weights import _draw, seed_words


def dims(cfg: dict) -> dict:
    """Sizes of one configuration file; the Mamba2 keys (H, P, G, N, chunk)
    are those ``bench.weights.dims`` gives, so that the SSD counts and
    readers serve both families."""
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    L = cfg["num_hidden_layers"]
    types = tuple(cfg["layer_types"][:L])
    return {
        "L": L, "d": d, "di": di, "H": cfg["mamba_n_heads"], "P": cfg["mamba_d_head"], "G": G, "N": N,
        "K": cfg["mamba_d_conv"], "conv": di + 2 * G * N, "V": cfg["vocab_size"],
        "Vp": -(-cfg["vocab_size"] // 128) * 128, "chunk": cfg["mamba_chunk_size"],
        "tied": bool(cfg["tie_word_embeddings"]),
        "Hq": cfg["num_attention_heads"], "Kv": cfg["num_key_value_heads"],
        "hd": d // cfg["num_attention_heads"],
        "E": cfg["reduced"]["num_local_experts"]["published"], "held": tuple(cfg["experts_held"]),
        "k": cfg["num_experts_per_tok"], "f": cfg["intermediate_size"], "fs": cfg["shared_intermediate_size"],
        "types": types, "Lm": types.count("mamba"), "La": types.count("attention"),
        "emb_mult": float(cfg["embedding_multiplier"]), "res_mult": float(cfg["residual_multiplier"]),
        "logit_div": float(cfg["logits_scaling"]), "attn_mult": float(cfg["attention_multiplier"]),
        "eps": float(cfg["rms_norm_eps"]), "conv_bias": bool(cfg["mamba_conv_bias"]),
    }


def program_config(cfg: dict, z: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig, MoEConfig, SSMConfig

    if cfg["position_embedding_type"] != "nope" or cfg["attention_bias"] or cfg["mamba_proj_bias"]:
        raise ValueError("the program runs NoPE attention with no attention or Mamba2 projection biases")
    return ModelConfig(
        name=cfg["name"], family="mamba_hybrid", num_layers=z["L"], d_model=z["d"],
        num_heads=z["Hq"], num_kv_heads=z["Kv"], d_ff=0, vocab_size=z["V"], head_dim=z["hd"],
        vocab_multiple=128, tied_embeddings=z["tied"],
        moe=MoEConfig(num_experts=z["E"], top_k=z["k"], d_ff_expert=z["f"], d_ff_shared=z["fs"],
                      experts_held=z["held"]),
        ssm=SSMConfig(d_state=z["N"], d_conv=z["K"], expand=z["di"] // z["d"], head_dim=z["P"],
                      chunk=z["chunk"], n_groups=z["G"], conv_bias=z["conv_bias"]),
        layer_types=z["types"], embedding_multiplier=z["emb_mult"], residual_multiplier=z["res_mult"],
        logits_scaling=z["logit_div"], attention_multiplier=z["attn_mult"], norm_eps=z["eps"],
        dtype=cfg["torch_dtype"],
    )


def _leaves(z: dict):
    """(path, shape, rule) of every parameter; rule is (kind, a, b)."""
    L, Lm, La, d, di, H, GN = z["L"], z["Lm"], z["La"], z["d"], z["di"], z["H"], z["G"] * z["N"]
    Eh, f, fs, Hq, Kv, hd = len(z["held"]), z["f"], z["fs"], z["Hq"], z["Kv"], z["hd"]
    n = lambda s: ("normal", s, 0.0)  # noqa: E731

    def fan(s: float, fan_in: int, published: int):
        """N(0, s) at the published fan-in, scaled as 1/√fan-in."""
        return n(s * (published / fan_in) ** 0.5)

    out = [(("embed", "tok"), (z["Vp"], d), fan(0.001, d, 4096)), (("ln_f", "w"), (d,), n(0.1))]
    if Lm:
        out += [
            (("mamba", "ln", "w"), (Lm, d), n(0.1)),
            (("mamba", "wz"), (Lm, d, di), fan(0.02, d, 4096)),
            (("mamba", "wx"), (Lm, d, di), fan(0.02, d, 4096)),
            (("mamba", "wB"), (Lm, d, GN), fan(0.02, d, 4096)),
            (("mamba", "wC"), (Lm, d, GN), fan(0.02, d, 4096)),
            (("mamba", "wdt"), (Lm, d, H), fan(0.02, d, 4096)),
            (("mamba", "conv_w"), (Lm, z["K"], z["conv"]), n(0.3)),
            *([(("mamba", "conv_b"), (Lm, z["conv"]), n(0.1))] if z["conv_bias"] else []),
            (("mamba", "A_log"), (Lm, H), ("log_uniform", 1.0, 16.0)),
            (("mamba", "D"), (Lm, H), ("uniform", 0.5, 1.5)),
            (("mamba", "dt_bias"), (Lm, H), ("inv_softplus_uniform", 1e-3, 0.1)),
            (("mamba", "norm_g"), (Lm, di), n(0.1)),
            (("mamba", "wo"), (Lm, di, d), fan(0.02, di, 8192)),
        ]
    if La:
        out += [
            (("attn", "ln", "w"), (La, d), n(0.1)),
            (("attn", "wq"), (La, d, Hq, hd), fan(0.08, d, 4096)),
            (("attn", "wk"), (La, d, Kv, hd), fan(0.08, d, 4096)),
            (("attn", "wv"), (La, d, Kv, hd), fan(0.04, d, 4096)),
            (("attn", "wo"), (La, Hq, hd, d), fan(0.02, Hq * hd, 4096)),
        ]
    out += [
        (("moe", "ln", "w"), (L, d), n(0.1)),
        (("moe", "router"), (L, d, z["E"]), fan(0.05, d, 4096)),
        (("moe", "w_gate"), (L, Eh, d, f), fan(0.02, d, 4096)),
        (("moe", "w_up"), (L, Eh, d, f), fan(0.02, d, 4096)),
        (("moe", "w_down"), (L, Eh, f, d), fan(0.1, f, 768)),
        (("moe", "shared_gate"), (L, d, fs), fan(0.02, d, 4096)),
        (("moe", "shared_up"), (L, d, fs), fan(0.02, d, 4096)),
        (("moe", "shared_down"), (L, fs, d), fan(0.02, fs, 1536)),
    ]
    return out


@functools.lru_cache(maxsize=None)
def _maker(zkey: tuple, dtype: str):
    leaves = _leaves(dict(zkey))

    @jax.jit
    def make(words):
        key = jax.random.wrap_key_data(words, impl="threefry2x32")
        out: dict = {}
        for i, (path, shape, rule) in enumerate(leaves):
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = _draw(jax.random.fold_in(key, i), shape, rule).astype(dtype)
        return out

    return make


def make_weights(cfg: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """The weights of ``cfg`` for ``seed``, in ``dtype``, on the default device."""
    z = dims(cfg)
    return _maker(tuple(sorted(z.items())), dtype)(jnp.asarray(seed_words(seed)))
