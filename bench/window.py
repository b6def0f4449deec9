"""The measured window, shared by every kind of cell.

A THAPI session runs around the window (the tracer is the system under
test, so it is on in every run of a cell).  With ``--trace 1`` the JAX profiler
records the window's leading ``profile_seconds`` (python tracing off), with
the harness's own host spans in it; stopping the profiler is left out of
the window's time.  Compiles inside the window are counted.
"""

from __future__ import annotations

import contextlib
import os
import time

_COMPILES = []  # one entry per backend compile in this process


def _count_compiles() -> None:
    import jax

    if not _COMPILES:
        _COMPILES.append("listener")
        jax.monitoring.register_event_duration_secs_listener(
            lambda ev, dur, **kw: _COMPILES.append(ev) if ev.endswith("backend_compile_duration") else None)


def program_config(cfg: dict, z: dict, **overrides):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig, SSMConfig

    s = cfg["ssm_cfg"]
    return ModelConfig(
        name=cfg["name"],
        family="ssm",
        num_layers=z["L"],
        d_model=z["d"],
        num_heads=z["H"],
        num_kv_heads=z["H"],
        d_ff=0,
        vocab_size=z["V"],
        head_dim=z["P"],
        vocab_multiple=cfg["pad_vocab_size_multiple"],
        tied_embeddings=cfg["tie_embeddings"],
        ssm=SSMConfig(d_state=z["N"], d_conv=z["K"], expand=s["expand"], head_dim=z["P"],
                      chunk=z["chunk"], n_groups=z["G"]),
        dtype=cfg["torch_dtype"],
        **overrides,
    )


class Window:
    def __init__(self, seconds: float, trace: bool, mix: dict, tmp: str, t_process: float):
        _count_compiles()
        self.seconds, self.trace, self.mix, self.tmp = seconds, trace, mix, tmp
        self.t_process = t_process
        self.profile_dir = os.path.join(tmp, "profile")
        self.profiling = False
        self.paused = 0.0

    def span(self, name: str):
        """A host span in the profiler's trace, while it records."""
        if not self.profiling:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        import jax

        from repro.core import TraceConfig, Tracer

        self.tracer = Tracer(TraceConfig(out_dir=os.path.join(self.tmp, "thapi"),
                                         mode=self.mix["thapi_mode"])).start()
        self.n_compiles = len(_COMPILES)
        self.t_start = time.perf_counter()
        self.setup_s = self.t_start - self.t_process
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.profile_dir, profiler_options=opts)
            self.profiling = True
            self._annot = jax.profiler.TraceAnnotation("bench.window")
            self._annot.__enter__()
            self.t_start = time.perf_counter()
            self.prof_until = self.t_start + self.mix["profile_seconds"]
        self.t_end = self.t_start + self.seconds

    def _stop_profiler(self) -> None:
        import jax

        self._annot.__exit__(None, None, None)
        self.profiling = False
        jax.profiler.stop_trace()

    def tick(self) -> bool:
        """After each unit of work: True while the window lasts."""
        t = time.perf_counter()
        self.t_last = t
        if self.profiling and t >= self.prof_until:
            self._stop_profiler()
            self.paused = time.perf_counter() - t
            self.t_end += self.paused
        return t < self.t_end

    def close(self):
        """End the window; returns (THAPI handle, tally, seconds from
        ``Tracer.stop()`` to the tally in hand)."""
        from repro.core.plugins.tally import tally_trace

        if self.profiling:
            self._stop_profiler()
        self.window_s = self.t_last - self.t_start - self.paused
        self.compiles_in_window = len(_COMPILES) - self.n_compiles
        t0 = time.perf_counter()
        handle = self.tracer.stop()
        tally = tally_trace(handle.trace_dir)
        return handle, tally, time.perf_counter() - t0


def calls(tally, api: str) -> int:
    return sum(st.calls for (_, a), st in tally.apis.items() if a == api)


def span_seconds(tally, api: str) -> float:
    return sum(st.total_ns for (_, a), st in tally.apis.items() if a == api) / 1e9


def unreported_calls(made: dict, tally, handle) -> tuple:
    """(calls the THAPI tally misses or adds against the calls the window
    made, beyond what it reports as dropped; what it tallied)."""
    seen = {api: calls(tally, api) for api in made}
    mismatch = sum(abs(made[a] - seen[a]) for a in made)
    return max(0, mismatch - (handle.dropped + tally.discarded)), seen


def device_peak_bytes() -> int:
    from repro.core.telemetry import read_device_memory

    return max(p for _, p, _ in read_device_memory())


def reduce_trace(ctx: dict, log) -> None:
    """Reduce the profiler's trace of a ``--trace 1`` run into ``ctx``."""
    import glob

    from bench.reduce import reduce_profile

    paths = glob.glob(os.path.join(ctx["profile_dir"], "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    ctx["profile"] = reduce_profile(paths[0], window="bench.window")
    log(f"[bench] profile: {ctx['profile']['summary']}")
