"""Interception library (THAPI §3.2–§3.3, Fig 1b "Wrapper Functions").

THAPI intercepts every API entry/exit of each programming model and records
the *complete call context* — arguments, pointers, sizes, out-values — not
just names and timestamps (§1.1's zeCommandListAppendMemoryCopy example).

Our stack's interception points:

  ust_repro       framework API   — train/eval/data/checkpoint/serve calls
  ust_jaxrt       JAX dispatch    — jit dispatches, compiles, h2d/d2h copies
  ust_kernel      device layer    — kernel/transfer spans with device times
  ust_collective  comm layer      — collective spans (bytes, axis, #devices)

Pointer convention reproduced from the paper: host addresses start ``0x00``,
device addresses ``0xff`` — so analysis can deduce H2D vs D2H from the
address classes alone, exactly the deduction §1.1 walks through.

Everything here no-ops at one branch when tracing is off: the recorders
generated by tracepoints.py check their enable flag first, and the wrapper
fast paths check ``active_tracer()`` once.
"""

from __future__ import annotations

import functools
import re
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import numpy as np

from .clock import now
from .telemetry import TransferGauge
from .tracer import active_tracer, get_tracepoints

# Transfer kinds (ust_jaxrt:memcpy / ust_kernel:transfer `kind` field)
H2D, D2H, D2D = 0, 1, 2

_DEVICE_PTR_TAG = 0xFF00_0000_0000_0000
_PTR_MASK = 0x0000_FFFF_FFFF_FFFF


def host_ptr(x: Any) -> int:
    """Host address (0x00-prefixed). Real for numpy, synthetic otherwise."""
    if isinstance(x, np.ndarray):
        return x.ctypes.data & _PTR_MASK
    return id(x) & _PTR_MASK


def device_ptr(x: Any) -> int:
    """Synthetic 0xff-prefixed device address (paper §1.1 convention).

    On real TPU this binds to ``x.addressable_shards[0].data.unsafe_buffer_pointer()``.
    """
    try:
        return _DEVICE_PTR_TAG | (x.unsafe_buffer_pointer() & _PTR_MASK)
    except Exception:
        return _DEVICE_PTR_TAG | (id(x) & _PTR_MASK)


def nbytes_of(tree: Any) -> int:
    return sum(
        getattr(leaf, "nbytes", 0) for leaf in jax.tree_util.tree_leaves(tree)
    )


class _TP:
    """Late-bound recorder bundle (single global trace model)."""

    def __init__(self):
        tps = get_tracepoints()
        r = tps.record
        self.dispatch_entry = r["ust_jaxrt:dispatch_entry"]
        self.dispatch_exit = r["ust_jaxrt:dispatch_exit"]
        self.compile_entry = r["ust_jaxrt:compile_entry"]
        self.compile_exit = r["ust_jaxrt:compile_exit"]
        self.memcpy_entry = r["ust_jaxrt:memcpy_entry"]
        self.memcpy_exit = r["ust_jaxrt:memcpy_exit"]
        self.alloc_entry = r["ust_jaxrt:alloc_entry"]
        self.alloc_exit = r["ust_jaxrt:alloc_exit"]
        self.block_entry = r["ust_jaxrt:block_until_ready_entry"]
        self.block_exit = r["ust_jaxrt:block_until_ready_exit"]
        self.poll_entry = r["ust_repro:poll_ready_entry"]
        self.poll_exit = r["ust_repro:poll_ready_exit"]
        # fused entry/exit pairs: one ring reservation for both records —
        # used where the wrapper holds all exit values when it records
        # (memcpy/alloc/poll); dispatch/compile/block keep separate calls
        # because user work runs between their entry and exit
        p = tps.record_pair
        self.memcpy_pair = p["ust_jaxrt:memcpy"]
        self.alloc_pair = p["ust_jaxrt:alloc"]
        self.poll_pair = p["ust_repro:poll_ready"]
        self.queue_wait_pair = p["ust_repro:queue_wait"]
        self.moe_route_pair = p["ust_repro:moe_route"]
        self.kernel_span = r["ust_kernel:launch_span"]
        self.transfer_span = r["ust_kernel:transfer_span"]
        self.coll = {
            k: r[f"ust_collective:{k}_span"]
            for k in ("all_reduce", "all_gather", "reduce_scatter", "all_to_all", "broadcast", "barrier")
        }
        self.fw = {
            name: (r[f"ust_repro:{name}_entry"], r[f"ust_repro:{name}_exit"])
            for name in (
                "train_step",
                "eval_step",
                "data_next",
                "checkpoint_save",
                "checkpoint_restore",
                "optimizer_update",
                "prefill",
                "decode_step",
                "engine_step",
            )
        }


_tp: Optional[_TP] = None


def tp() -> _TP:
    global _tp
    if _tp is None:
        _tp = _TP()
    return _tp


# ---------------------------------------------------------------------------
# ust_jaxrt: traced jit dispatch (the workhorse wrapper)
# ---------------------------------------------------------------------------


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name`` made an identifier, so that jit names its
    program ``jit_<name>`` (the profiler's module and ``PjitFunction``
    names) whatever ``fn`` is called, a lambda included."""

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = re.sub(r"\W+", "_", name).strip("_") or "fn"
    return program


class TracedJit:
    """jax.jit wrapper with THAPI interception.

    Records per call:
      * ``dispatch`` entry/exit — fn name, #args, argument bytes, donated bytes
        (the jitted program takes the name too: ``jit_<name>``, made an
        identifier, in the compiler's and the profiler's records)
      * ``compile`` entry/exit on first trace (cache_hit=False) — ≙ module load
      * ``launch`` span — device begin/end timestamps around execution
        (GPU-profiling meta-parameter, Fig 2 Scenario 2). Device completion is
        fenced inside a ``block_until_ready`` span; in *full* mode the wait
        is a polling loop emitting ``poll_ready`` events — the spin-lock
        pattern behind zeEventHostSynchronize's 9.9M calls in the §4.3 tally.
    """

    def __init__(
        self,
        fn: Callable,
        name: Optional[str] = None,
        *,
        donate_argnums: Sequence[int] = (),
        static_argnums: Sequence[int] = (),
        in_shardings: Any = None,
        out_shardings: Any = None,
        flops: int = 0,
        bytes_accessed: int = 0,
        profiled: bool = True,
    ):
        kw: Dict[str, Any] = dict(
            donate_argnums=tuple(donate_argnums), static_argnums=tuple(static_argnums)
        )
        if in_shardings is not None:
            kw["in_shardings"] = in_shardings
        if out_shardings is not None:
            kw["out_shardings"] = out_shardings
        self.name = name or getattr(fn, "__name__", "jit_fn")
        self._jit = jax.jit(_named(fn, self.name), **kw)
        self.donate_argnums = frozenset(donate_argnums)
        self.flops = flops
        self.bytes_accessed = bytes_accessed
        self.profiled = profiled
        self._arg_bytes_cache: Optional[tuple] = None
        self._compiled_once = False

    # expose the underlying jit for lower()/compile() (dry-run path)
    @property
    def jit(self):
        return self._jit

    def lower(self, *a, **k):
        return self._jit.lower(*a, **k)

    def _sizes(self, args) -> tuple:
        if self._arg_bytes_cache is None:
            total = 0
            donated = 0
            for i, a in enumerate(args):
                nb = nbytes_of(a)
                total += nb
                if i in self.donate_argnums:
                    donated += nb
            self._arg_bytes_cache = (total, donated)
        return self._arg_bytes_cache

    def __call__(self, *args):
        tr = active_tracer()
        if tr is None or not tr.selected or tr.fidelity == "off":
            return self._jit(*args)
        t = tp()
        arg_bytes, donated = self._sizes(args)
        if not self._compiled_once:
            t.compile_entry(self.name, hash(self.name) & 0xFFFFFFFFFFFF)
            self._compiled_once = True
            first = True
        else:
            first = False
        t.dispatch_entry(self.name, len(args), arg_bytes, donated)
        ts_begin = now()
        out = self._jit(*args)
        if first:
            t.compile_exit(0, False)
        if self.profiled:
            self._fence(out, tr)
            ts_end = now()
            t.kernel_span(
                ts_begin, ts_end, self.name, 1, 1, 1, self.flops, self.bytes_accessed
            )
        t.dispatch_exit(0)
        return out

    def _fence(self, out, tr) -> None:
        """Wait for the device: one ``block_until_ready`` span per call.  In
        full mode the wait inside it is a polling loop that emits
        ``poll_ready`` events while the device works."""
        th = tp()
        th.block_entry(0)
        if tr.full:
            # The fused pair recorder + hoisted lookups keep the spin loop
            # itself off the measurement: one recorder call per poll, no
            # attribute chasing per iteration (§4.3's 9.9M-call pattern).
            leaves = [x for x in jax.tree_util.tree_leaves(out) if hasattr(x, "is_ready")]
            if leaves:
                h = device_ptr(leaves[-1])
                poll = th.poll_pair
                _now = now
                spins = 0
                while not all(x.is_ready() for x in leaves):
                    poll(h, _now(), False)
                    spins += 1
                    if spins > 4:
                        time.sleep(0)
                poll(h, _now(), True)
        else:
            jax.block_until_ready(out)
        th.block_exit(0)


def traced_jit(fn: Optional[Callable] = None, name: Optional[str] = None, **kw):
    """Decorator / factory form of :class:`TracedJit`."""
    if fn is None:
        return lambda f: TracedJit(f, name, **kw)
    return TracedJit(fn, name, **kw)


# ---------------------------------------------------------------------------
# ust_jaxrt: traced transfers + allocation
# ---------------------------------------------------------------------------


def traced_device_put(x: Any, sharding: Any = None, *, kind: int = H2D):
    """jax.device_put with memcpy interception (the paper's running example).

    Entry payload carries src/dst pointers + size; in full mode the first 32
    payload bytes are dumped (``ArgDump`` meta-parameter — "values behind
    pointers", §1.1).
    """
    tr = active_tracer()
    if tr is None or not tr.selected or tr.fidelity == "off":
        return jax.device_put(x, sharding) if sharding is not None else jax.device_put(x)
    t = tp()
    _now = now
    nb = nbytes_of(x)
    src = host_ptr(x) if kind == H2D else device_ptr(x)
    head = b""
    if tr.full and isinstance(x, np.ndarray):
        head = x.tobytes()[:32]
    ts_begin = _now()
    out = jax.device_put(x, sharding) if sharding is not None else jax.device_put(x)
    dst = device_ptr(out) if kind != D2H else host_ptr(out)
    jax.block_until_ready(out)
    ts_end = _now()
    # fused pair: one ring reservation frames entry+exit; the entry keeps the
    # pre-copy timestamp (truer to the API-entry instant than the legacy
    # post-copy stamp)
    t.memcpy_pair(src, dst, nb, kind, head, ts_begin, 0)
    t.transfer_span(ts_begin, ts_end, nb, kind)
    TransferGauge.bump_memcpy(nb)
    return out


def traced_device_get(x: Any) -> np.ndarray:
    """device → host readback, traced as a D2H memcpy."""
    tr = active_tracer()
    if tr is None or not tr.selected or tr.fidelity == "off":
        return np.asarray(x)
    t = tp()
    _now = now
    nb = nbytes_of(x)
    ts_begin = _now()
    out = np.asarray(x)
    ts_end = _now()
    t.memcpy_pair(device_ptr(x), host_ptr(out), nb, D2H, b"", ts_begin, 0)
    t.transfer_span(ts_begin, ts_end, nb, D2H)
    TransferGauge.bump_memcpy(nb)
    return out


def record_alloc(nbytes: int, device: int = 0) -> int:
    tr = active_tracer()
    ts = now()
    ptr = _DEVICE_PTR_TAG | (ts & _PTR_MASK)
    if tr is not None and tr.selected and tr.fidelity != "off":
        tp().alloc_pair(nbytes, device, ts, ptr)
        TransferGauge.bump_alloc(nbytes)
    return ptr


# ---------------------------------------------------------------------------
# ust_kernel / ust_collective spans
# ---------------------------------------------------------------------------


@contextmanager
def kernel_span(name: str, grid: Sequence[int] = (1, 1, 1), flops: int = 0, bytes_accessed: int = 0):
    """Device-span interception for hand-written (Pallas) kernels."""
    tr = active_tracer()
    if tr is None or not tr.selected or tr.fidelity == "off":
        yield
        return
    gx, gy, gz = (tuple(grid) + (1, 1, 1))[:3]
    rec = tp().kernel_span  # hoisted: no lookups on the span exit path
    _now = now
    ts_begin = _now()
    yield
    rec(ts_begin, _now(), name, gx, gy, gz, flops, bytes_accessed)


@contextmanager
def collective_span(kind: str, nbytes: int, axis: str, n_devices: int):
    tr = active_tracer()
    if tr is None or not tr.selected or tr.fidelity == "off":
        yield
        return
    rec = tp().coll[kind]
    _now = now
    ts_begin = _now()
    yield
    if kind == "broadcast":
        rec(ts_begin, _now(), nbytes, 0, n_devices)
    elif kind == "barrier":
        rec(ts_begin, _now(), axis, n_devices)
    else:
        rec(ts_begin, _now(), nbytes, axis, n_devices)


# ---------------------------------------------------------------------------
# ust_repro framework spans (≙ OMPT callbacks layer)
# ---------------------------------------------------------------------------


class _FrameworkSpan:
    """Entry/exit pair with OutScalar exit fields (Fig 3 meta-parameters).

    ``outs`` is filled by the caller before exit; missing outs default to 0.
    """

    __slots__ = ("_exit_rec", "_out_names", "outs", "_on")

    def __init__(self, api: str, entry_args: tuple, out_names: tuple):
        tr = active_tracer()
        self._on = tr is not None and tr.selected and tr.fidelity != "off"
        self._out_names = out_names
        self.outs: Dict[str, Any] = {}
        if self._on:
            entry_rec, self._exit_rec = tp().fw[api]
            entry_rec(*entry_args)

    def __enter__(self):
        return self

    def __exit__(self, et, ev, tb):
        if self._on:
            status = 0 if et is None else 1
            vals = [self.outs.get(n, 0) for n in self._out_names]
            self._exit_rec(status, *vals)
        return False


def train_step_span(step: int, global_batch: int, seq_len: int) -> _FrameworkSpan:
    return _FrameworkSpan("train_step", (step, global_batch, seq_len), ("loss", "grad_norm"))


def eval_step_span(step: int, global_batch: int) -> _FrameworkSpan:
    return _FrameworkSpan("eval_step", (step, global_batch), ("loss",))


def data_next_span(step: int) -> _FrameworkSpan:
    return _FrameworkSpan("data_next", (step,), ("tokens",))


def checkpoint_save_span(step: int, path: str, nbytes: int) -> _FrameworkSpan:
    return _FrameworkSpan("checkpoint_save", (step, path, nbytes), ())


def checkpoint_restore_span(path: str) -> _FrameworkSpan:
    return _FrameworkSpan("checkpoint_restore", (path,), ("step",))


def optimizer_update_span(step: int) -> _FrameworkSpan:
    return _FrameworkSpan("optimizer_update", (step,), ("lr",))


def prefill_span(request_id: int, batch: int, seq_len: int) -> _FrameworkSpan:
    return _FrameworkSpan("prefill", (request_id, batch, seq_len), ())


def decode_step_span(request_id: int, batch: int, cache_len: int) -> _FrameworkSpan:
    return _FrameworkSpan("decode_step", (request_id, batch, cache_len), ("tokens_out",))


def engine_step_span(step: int, active: int) -> _FrameworkSpan:
    """The whole of one serve step: admissions, decode, readback and
    bookkeeping; outs ``admitted`` and ``tokens_out``."""
    return _FrameworkSpan("engine_step", (step, active), ("admitted", "tokens_out"))


def record_queue_wait(request_id: int, since_ns: int) -> None:
    """One ``queue_wait`` pair for a request admitted now: its entry at
    ``since_ns``, the trace-clock stamp taken when it was submitted."""
    tr = active_tracer()
    if tr is not None and tr.selected and tr.fidelity != "off":
        tp().queue_wait_pair(request_id, since_ns, 0)


def record_moe_route(decode: bool, pairs: int, max_load: int, held: int) -> None:
    """One ``moe_route`` pair for a prefill or decode step whose route
    counts were just read back."""
    tr = active_tracer()
    if tr is not None and tr.selected and tr.fidelity != "off":
        tp().moe_route_pair(decode, held, now(), 0, pairs, max_load)
