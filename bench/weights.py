"""Seeded Mamba2 weights, made on the device in one jitted call.

The benchmark makes the weights itself, so that the program under test and
the plain reference read the same numbers and neither takes anything the
other made.  The layout is the one the served model takes
(``{"embed": {"tok"[, "head"]}, "blocks": {...}, "ln_f": {"w"}}``, layers
stacked on the leading axis, no ``head`` where the configuration ties it to
the embedding); the reference reads it by these names.

Scales: the projections are N(0, 0.02) as in Mamba2's initialisation, but
the temporal conv, the norm gains and the skip ``D`` are set so that the
conv, the SSD scan and the skip path each carry a comparable share of a
block's output.  With 0.02 everywhere the scan adds about 1% to the skip
term, and a broken scan would still agree with the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg: dict) -> dict:
    """Sizes of one configuration file (``bench/configs/*.json``)."""
    s = cfg["ssm_cfg"]
    d = cfg["d_model"]
    di = s["expand"] * d
    nh = di // s["headdim"]
    G, N = s["ngroups"], s["d_state"]
    m = cfg["pad_vocab_size_multiple"]
    return {
        "L": cfg["n_layer"], "d": d, "di": di, "H": nh, "P": s["headdim"], "G": G, "N": N,
        "K": s["d_conv"], "conv": di + 2 * G * N, "V": cfg["vocab_size"],
        "Vp": -(-cfg["vocab_size"] // m) * m, "chunk": s["chunk_size"],
        "tied": bool(cfg["tie_embeddings"]),
    }


def seed_words(seed: int) -> np.ndarray:
    """Any whole number as the two 32-bit words of a threefry key."""
    seed = int(seed) % (1 << 64)
    return np.array([seed >> 32, seed & 0xFFFFFFFF], dtype=np.uint32)


def _leaves(z: dict):
    """(path, shape, rule) of every parameter; rule is (kind, a, b)."""
    L, d, di, H, GN = z["L"], z["d"], z["di"], z["H"], z["G"] * z["N"]
    head = [] if z["tied"] else [(("embed", "head"), (d, z["Vp"]), ("normal", 0.02, 0.0))]
    return [
        (("embed", "tok"), (z["Vp"], d), ("normal", 0.02, 0.0)),
        *head,
        (("blocks", "ln", "w"), (L, d), ("normal", 0.1, 0.0)),
        (("blocks", "wz"), (L, d, di), ("normal", 0.02, 0.0)),
        (("blocks", "wx"), (L, d, di), ("normal", 0.02, 0.0)),
        (("blocks", "wB"), (L, d, GN), ("normal", 0.02, 0.0)),
        (("blocks", "wC"), (L, d, GN), ("normal", 0.02, 0.0)),
        (("blocks", "wdt"), (L, d, H), ("normal", 0.02, 0.0)),
        (("blocks", "conv_w"), (L, z["K"], z["conv"]), ("normal", 0.5, 0.0)),
        # A = -uniform[1, 16], kept as log; dt bias = softplus^-1(uniform[1e-3, 0.1])
        (("blocks", "A_log"), (L, H), ("log_uniform", 1.0, 16.0)),
        (("blocks", "D"), (L, H), ("uniform", 0.5, 1.5)),
        (("blocks", "dt_bias"), (L, H), ("inv_softplus_uniform", 1e-3, 0.1)),
        (("blocks", "norm_g"), (L, di), ("normal", 0.1, 0.0)),
        (("blocks", "wo"), (L, di, d), ("normal", 0.02, 0.0)),
        (("ln_f", "w"), (d,), ("normal", 0.1, 0.0)),
    ]


def _draw(key, shape, rule):
    kind, a, b = rule
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * a
    u = jax.random.uniform(key, shape, jnp.float32, a, b)
    if kind == "log_uniform":
        return jnp.log(u)
    if kind == "inv_softplus_uniform":
        return jnp.log(jnp.expm1(u))
    return u


@functools.lru_cache(maxsize=None)
def _maker(cfg_key: tuple, dtype: str):
    z = dict(cfg_key)
    leaves = _leaves(z)

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
    """The weights of ``cfg`` for ``seed``, in ``dtype``, on the default device.
    The same seed gives the same weights; one compile serves every seed."""
    z = dims(cfg)
    return _maker(tuple(sorted(z.items())), dtype)(jnp.asarray(seed_words(seed)))


def reference_weights(cfg: dict, seed: int) -> dict:
    """The served (rounded) weights in float32, for the reference."""
    w = make_weights(cfg, seed, cfg["torch_dtype"])
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
