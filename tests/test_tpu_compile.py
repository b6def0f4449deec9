"""The main path's Pallas kernels compile for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and refuses what the chip would refuse (block shapes off the
(8, 128) tiling, unsupported primitives, too much VMEM) — which interpret
mode, where the other kernel tests run, cannot show.  Shapes are the
published widths of the models that call each kernel.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and every test worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.ssd_scan import ssd_pallas
from repro.kernels.ssd_step import ssd_step_pallas
from repro.models import Model, ShapeSpec
from repro.models.param import shapes as spec_shapes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


bf16, f32 = jnp.bfloat16, jnp.float32


def test_ssd_compiles_for_v5e(one_chip):
    """mamba2-1.3b: H 64, P 64, N 128, G 1, chunk 256, S 512."""
    B, S, H, P, G, N = 2, 512, 64, 64, 1, 128
    text = _compile(
        lambda *a: ssd_pallas(*a, chunk=256),
        [((B, S, H, P), bf16), ((B, S, H), f32), ((H,), f32),
         ((B, S, G, N), bf16), ((B, S, G, N), bf16), ((H,), f32)],
        one_chip,
    )
    assert "tpu_custom_call" in text


def test_rglru_compiles_for_v5e(one_chip):
    """recurrentgemma-2b: lru width C 2560, batch > 1 (h0 tiling)."""
    B, S, C = 2, 512, 2560
    text = _compile(
        rglru_pallas,
        [((B, S, C), bf16), ((B, S, C), bf16), ((B, S, C), bf16), ((C,), f32), ((B, C), f32)],
        one_chip,
    )
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_for_v5e(one_chip):
    """h2o-danube-1.8b: 32 heads, 8 kv heads, head dim 80, windowed."""
    B, S, H, Kv, hd = 1, 2048, 32, 8, 80
    text = _compile(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True, window=512),
        [((B, S, H, hd), bf16), ((B, S, Kv, hd), bf16), ((B, S, Kv, hd), bf16)],
        one_chip,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["ssd", "rglru"])
def test_kernel_gradient_compiles_for_v5e(one_chip, kernel, monkeypatch):
    """The train path: the custom_vjp (Pallas forward, reference-VJP
    backward) compiles for the chip, the kernel still in the program."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)  # the chip's path
    if kernel == "ssd":
        B, S, H, P, G, N = 2, 512, 64, 64, 1, 128
        shapes = [((B, S, H, P), bf16), ((B, S, H), f32), ((H,), f32),
                  ((B, S, G, N), bf16), ((B, S, G, N), bf16), ((H,), f32)]

        def loss(*a):
            y, st = ops.ssd(*a, chunk=256, impl="pallas")
            return jnp.sum(y.astype(f32) ** 2) + jnp.sum(st)
    else:
        B, S, C = 2, 512, 2560
        shapes = [((B, S, C), bf16), ((B, S, C), bf16), ((B, S, C), bf16), ((C,), f32), ((B, C), f32)]

        def loss(*a):
            y, h = ops.rglru(*a, impl="pallas")
            return jnp.sum(y.astype(f32) ** 2) + jnp.sum(h)

    text = _compile(jax.grad(loss, argnums=tuple(range(len(shapes)))), shapes, one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("L,H,G", [(48, 64, 1), (9, 128, 1), (4, 64, 8)])
def test_ssd_step_compiles_for_v5e(one_chip, L, H, G):
    """The decode kernel on a stacked state at 64 slots, heads of 64, state
    128: mamba2-1.3b (48 layers of 64 heads) and granite-4.0-h-small (its
    stage's nine Mamba2 layers of 128 heads); and eight B/C groups, whose
    head blocks of 8 are smaller than the lane tile."""
    B, P, N = 64, 64, 128
    text = _compile(
        ssd_step_pallas,
        [((L, B, H, P, N), bf16), ((), jnp.int32), ((B, H, P), bf16), ((B, H), f32), ((H,), f32),
         ((B, G, N), bf16), ((B, G, N), bf16), ((H,), f32)],
        one_chip,
    )
    assert "tpu_custom_call" in text


def test_mamba2_decode_step_updates_state_in_place_on_v5e(one_chip, monkeypatch):
    """mamba2-1.3b's donated decode step at 64 slots on the chip's path: the
    state kernel is in the program, and no copy or broadcast of a whole state
    leaf is."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    cfg = get_config("mamba2-1.3b")
    m = Model(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree
        )

    cache = on_chip(spec_shapes(m.cache_specs(ShapeSpec("d", "decode", 4096, 64)), cfg.dtype))
    token = {"token": jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)}
    step = jax.jit(m.decode_step, donate_argnums=(1,))
    text = step.lower(on_chip(m.shapes()), cache, token).compile().as_text()
    assert re.search(r"ssd_step[.\d]* = .* custom-call\(", text)
    dims = ",".join(map(str, cache["state"].shape))
    whole = re.compile(r"= \w+\[%s\]\S* (copy|copy-start|broadcast)\(" % dims)
    offenders = [line.strip() for line in text.splitlines() if whole.search(line)]
    assert not offenders, offenders
