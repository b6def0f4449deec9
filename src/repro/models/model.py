"""Unified Model facade over the five family implementations.

One object per (config, optional mesh) exposing the API the trainer, server,
dry-run and benchmarks all share:

    m = Model(cfg, mesh)
    m.param_specs()           spec tree (shapes/axes/init in one declaration)
    m.init(rng) / m.shapes()  arrays / ShapeDtypeStructs
    m.loss(params, batch)     → (loss, metrics)
    m.prefill / m.decode_step serving steps → (logits, cache, route counts)
    m.batch_specs(shape)      input Spec tree for an assigned ShapeSpec
    m.cache_specs(shape)      serving-state Spec tree for decode shapes
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from . import encdec, hybrid, mamba_hybrid, moe, ssm, transformer
from .config import ModelConfig, ShapeSpec
from .layers import xent_loss
from .param import Spec, axes as spec_axes, init as spec_init, shapes as spec_shapes

_FAMILY = {
    "dense": transformer,
    "vlm": transformer,
    "moe": moe,
    "ssm": ssm,
    "hybrid": hybrid,
    "mamba_hybrid": mamba_hybrid,
    "audio": encdec,
}


class Model:
    def __init__(self, cfg: ModelConfig, mesh=None):
        self.cfg = cfg
        self.mesh = mesh
        self.mod = _FAMILY[cfg.family]

    # -- parameters ------------------------------------------------------------
    def param_specs(self):
        return self.mod.specs(self.cfg)

    def shapes(self):
        return spec_shapes(self.param_specs(), self.cfg.dtype)

    def axes(self):
        return spec_axes(self.param_specs())

    def init(self, rng):
        return spec_init(self.param_specs(), rng, self.cfg.dtype)

    # -- training ---------------------------------------------------------------
    def logits(self, params, batch):
        if self.cfg.family == "moe":
            out, aux = self.mod.forward_train(self.cfg, params, batch, mesh=self.mesh)
            return out, aux
        if self.cfg.family in ("ssm", "mamba_hybrid"):  # the SSD kernel runs per data shard
            out = self.mod.forward_train(self.cfg, params, batch, mesh=self.mesh)
        else:
            out = self.mod.forward_train(self.cfg, params, batch)
        return out, jnp.zeros((), jnp.float32)

    def loss(self, params, batch):
        logits, aux = self.logits(params, batch)
        labels = batch["labels"]
        if self.cfg.vision_tokens:  # loss only over the text positions
            logits = logits[:, self.cfg.vision_tokens :]
        ce = xent_loss(self.cfg, logits, labels)
        total = ce
        if self.cfg.family == "moe":
            total = ce + self.cfg.moe.aux_loss_weight * aux
        return total, {"ce": ce, "aux": aux}

    # -- serving -----------------------------------------------------------------
    def prefill(self, params, batch, cache_len: int):
        """(last-position logits, cache row, route counts) of a prompt."""
        if self.cfg.family in ("moe", "ssm", "mamba_hybrid"):
            out = self.mod.prefill(self.cfg, params, batch, cache_len, mesh=self.mesh)
        else:
            out = self.mod.prefill(self.cfg, params, batch, cache_len)
        return _with_route(out)

    def decode_step(self, params, cache, batch):
        """(logits, cache, route counts) of one token for every slot."""
        if self.cfg.family == "moe":
            out = self.mod.decode_step(self.cfg, params, cache, batch, mesh=self.mesh)
        else:
            out = self.mod.decode_step(self.cfg, params, cache, batch)
        return _with_route(out)

    # -- input/cache declarations (drive smoke tests AND the dry-run) -------------
    def batch_specs(self, shape: ShapeSpec) -> dict:
        B, S = shape.global_batch, shape.seq_len
        if shape.kind == "train":
            out = {
                "tokens": Spec((B, self._text_len(S)), ("batch", "seq"), dtype="int32"),
                "labels": Spec((B, self._text_len(S)), ("batch", "seq"), dtype="int32"),
            }
            self._add_frontend(out, B)
            return out
        if shape.kind == "prefill":
            out = {"tokens": Spec((B, self._text_len(S)), ("batch", "seq"), dtype="int32")}
            self._add_frontend(out, B)
            return out
        # decode: one token against a cache of length S
        return {"token": Spec((B,), ("batch",), dtype="int32")}

    def _text_len(self, S: int) -> int:
        return S - self.cfg.vision_tokens if self.cfg.vision_tokens else S

    def _add_frontend(self, out: dict, B: int) -> None:
        cfg = self.cfg
        if cfg.family == "audio":
            out["frames"] = Spec(
                (B, cfg.encdec.enc_positions, cfg.d_model), ("batch", None, "embed")
            )
        if cfg.vision_tokens:
            out["patch_embeds"] = Spec(
                (B, cfg.vision_tokens, cfg.d_model), ("batch", None, "embed")
            )

    def cache_specs(self, shape: ShapeSpec):
        return self.mod.cache_specs(self.cfg, shape.global_batch, shape.seq_len)

    # -- analytics ----------------------------------------------------------------
    def model_flops_per_token(self) -> int:
        """6·N_active — the §Roofline MODEL_FLOPS convention."""
        return 6 * self.cfg.active_params()


def _with_route(out):
    """A family's serving output as (logits, cache, route counts).  The
    counts are a family's with held experts (int32 [3]: token–expert pairs
    on the held experts, Σ over layers of the largest held expert's pairs,
    experts held), and an int32 [0] for every other family."""
    return out if len(out) == 3 else (*out, jnp.zeros((0,), jnp.int32))
