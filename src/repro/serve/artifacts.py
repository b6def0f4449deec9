"""Sharded prefill/decode step builders — executed by the engine at example
scale and lowered verbatim by the multi-pod dry-run for the inference shapes."""

from __future__ import annotations


import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import Model, ShapeSpec
from repro.models.param import axes as spec_axes, shapes as spec_shapes
from repro.sharding import Partitioner
from repro.train.train_step import _tree_pspecs


def _shardings(partitioner: Partitioner, shapes_tree, axes_tree):
    pspecs = _tree_pspecs(partitioner, shapes_tree, axes_tree)
    return jax.tree_util.tree_map(
        lambda ps: NamedSharding(partitioner.mesh, ps), pspecs, is_leaf=lambda x: isinstance(x, P)
    )


def param_artifacts(model: Model, partitioner: Partitioner):
    shapes = model.shapes()
    return shapes, _shardings(partitioner, shapes, model.axes())


def prefill_artifacts(model: Model, partitioner: Partitioner, shape: ShapeSpec):
    """jit + (param, batch) shapes/shardings for the prefill_* shapes.

    Output cache is sharded like cache_specs; logits and route counts
    unconstrained.
    """
    p_shapes, p_shardings = param_artifacts(model, partitioner)
    b_specs = model.batch_specs(shape)
    b_shapes = spec_shapes(b_specs, model.cfg.dtype)
    b_shardings = _shardings(partitioner, b_shapes, spec_axes(b_specs))
    c_specs = model.cache_specs(shape)
    c_shapes = spec_shapes(c_specs, model.cfg.dtype)
    c_shardings = _shardings(partitioner, c_shapes, spec_axes(c_specs))
    fn = jax.jit(
        lambda p, b: model.prefill(p, b, shape.seq_len),
        in_shardings=(p_shardings, b_shardings),
        out_shardings=(None, c_shardings, None),
    )
    return fn, (p_shapes, b_shapes), (p_shardings, b_shardings)


def decode_artifacts(model: Model, partitioner: Partitioner, shape: ShapeSpec):
    """jit + (params, cache, batch) shapes/shardings for decode_* / long_*.

    serve_step semantics: ONE new token against a cache of shape.seq_len.
    The cache is donated (in-place update in HBM).
    """
    p_shapes, p_shardings = param_artifacts(model, partitioner)
    c_specs = model.cache_specs(shape)
    c_shapes = spec_shapes(c_specs, model.cfg.dtype)
    c_shardings = _shardings(partitioner, c_shapes, spec_axes(c_specs))
    b_specs = model.batch_specs(shape)
    b_shapes = spec_shapes(b_specs, model.cfg.dtype)
    b_shardings = _shardings(partitioner, b_shapes, spec_axes(b_specs))
    fn = jax.jit(
        model.decode_step,
        in_shardings=(p_shardings, c_shardings, b_shardings),
        out_shardings=(None, c_shardings, None),
        donate_argnums=(1,),
    )
    return fn, (p_shapes, c_shapes, b_shapes), (p_shardings, c_shardings, b_shardings)
