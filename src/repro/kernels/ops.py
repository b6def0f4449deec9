"""jit-ready dispatch wrappers for the Pallas kernels.

Selection policy (:func:`default_impl`): on a TPU backend the wrappers run
the Pallas kernels; on any other backend they run the jnp references in
``ref.py``, and a caller that forces ``impl="pallas"`` there gets the
kernels under ``interpret=True`` (how the tests validate them on CPU).
``REPRO_KERNELS=ref|pallas`` overrides the backend's choice; the tracer
stamps the effective choice into the trace metadata so a run that swapped
the references in on a chip says so.

``ssd`` and ``rglru`` are differentiable on the kernel path: a
``jax.custom_vjp`` runs the Pallas kernel forward and takes the backward as
the VJP of the jnp reference.

The wrappers run while ``jit`` traces the model, once per compile, so they
record no THAPI span: a kernel's device time is in the device's own trace.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.jaxcompat import shard_map

from . import ref as _ref


def default_impl() -> str:
    """The path a wrapper takes when the caller names none: ``REPRO_KERNELS``
    when set, else ``pallas`` on TPU and ``ref`` elsewhere."""
    return os.environ.get("REPRO_KERNELS") or (
        "pallas" if jax.default_backend() == "tpu" else "ref"
    )


def _impl(impl: Optional[str]) -> str:
    return impl if impl is not None else default_impl()


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _per_shard(kernel, mesh, batched_in, batched_out):
    """Run ``kernel`` once per data-parallel shard of ``mesh``.

    XLA cannot partition a Mosaic kernel, so on a mesh of several devices
    the call goes through shard_map: arguments flagged in ``batched_in`` /
    results in ``batched_out`` split their leading (batch) dim over the
    mesh's data axes, everything else is replicated.
    """
    if mesh is None or mesh.size == 1:
        return kernel
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)

    def specs(flags):
        return tuple(P(dp) if f else P() for f in flags)

    return shard_map(kernel, mesh, specs(batched_in), specs(batched_out))


def _with_ref_vjp(kernel, reference):
    """``kernel`` forward, the VJP of the jnp ``reference`` backward."""

    @jax.custom_vjp
    def f(*args):
        return kernel(*args)

    def fwd(*args):
        return kernel(*args), args

    def bwd(args, g):
        return jax.vjp(reference, *args)[1](g)

    f.defvjp(fwd, bwd)
    return f


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------


def flash_attention(
    q, k, v, *, causal: bool = True, window: Optional[int] = None, scale: Optional[float] = None, impl=None
):
    """Scores scaled by ``scale``, 1/√head_dim when None."""
    if _impl(impl) == "pallas":
        from .flash_attention import flash_attention_pallas

        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, scale=scale, interpret=_interpret()
        )
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window, scale=scale)


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------


def rglru(x, r, i, lam, h0=None, *, impl=None):
    if _impl(impl) == "pallas":
        from .rglru_scan import rglru_pallas

        if h0 is None:
            B, _, C = x.shape
            h0 = jnp.zeros((B, C), jnp.float32)
        f = _with_ref_vjp(functools.partial(rglru_pallas, interpret=_interpret()), _ref.rglru_ref)
        return f(x, r, i, lam, h0)
    return _ref.rglru_ref(x, r, i, lam, h0=h0)


def rglru_step(h, x_t, r_t, i_t, lam):
    return _ref.rglru_step_ref(h, x_t, r_t, i_t, lam)


# ---------------------------------------------------------------------------
# Mamba2 SSD
# ---------------------------------------------------------------------------


def ssd(x, dt, A_log, Bm, Cm, D, *, chunk: int = 64, mesh=None, impl=None):
    if _impl(impl) == "pallas":
        from .ssd_scan import ssd_pallas

        f = _with_ref_vjp(
            _per_shard(
                functools.partial(ssd_pallas, chunk=chunk, interpret=_interpret()),
                mesh,
                (True, True, False, True, True, False),
                (True, True),
            ),
            functools.partial(_ref.ssd_ref, chunk=chunk),
        )
        return f(x, dt, A_log, Bm, Cm, D)
    return _ref.ssd_ref(x, dt, A_log, Bm, Cm, D, chunk=chunk)


def ssd_step_inplace(state, l, x_t, dt_t, A_log, B_t, C_t, D, *, impl=None):
    """One decode token through layer ``l`` of the stacked state
    [L,B,H,P,N] → (y_t [B,H,P], state with that layer's slice replaced).
    Loop-carried and donated, the state is updated in place on both paths."""
    if _impl(impl) == "pallas":
        from .ssd_step import ssd_step_pallas

        return ssd_step_pallas(state, l, x_t, dt_t, A_log, B_t, C_t, D, interpret=_interpret())
    st = lax.dynamic_index_in_dim(state, l, 0, keepdims=False)
    y, st = _ref.ssd_step_ref(st.astype(jnp.float32), x_t, dt_t, A_log, B_t, C_t, D)
    return y, lax.dynamic_update_index_in_dim(state, st.astype(state.dtype), l, 0)


def causal_conv1d(x, w, state=None):
    return _ref.causal_conv1d_ref(x, w, state=state)
