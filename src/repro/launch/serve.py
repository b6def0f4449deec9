"""Serving launcher: batched requests through the traced engine.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b \
        --requests 8 --prompt-len 256 512 --new-tokens 32 --slots 4

Serves the published config with random weights from seed 0 (``--smoke``
picks the reduced same-family config).  A warm-up request per prompt length
compiles every prefill program and the decode step first, so the timed
requests run compiled: set-up (init + compiles) and steady time print apart.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.core import TraceConfig, Tracer
from repro.core.plugins.tally import render, tally_trace
from repro.core.telemetry import device_info, read_device_memory
from repro.jaxcompat import enable_compile_cache
from repro.kernels.ops import default_impl
from repro.models import Model
from repro.serve import ServeConfig, ServeEngine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCHS)
    ap.add_argument("--smoke", action="store_true", help="reduced same-family config")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument(
        "--prompt-len", type=int, nargs="+", default=[16], help="prompt lengths, dealt in turn"
    )
    ap.add_argument("--report", default=None, help="write a JSON summary here")
    ap.add_argument("--trace", choices=["off", "minimal", "default", "full"], default="off")
    ap.add_argument("--trace-dir", default="/tmp/thapi_serve")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    t0 = time.perf_counter()
    model = Model(cfg)
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(0)))
    eng = ServeEngine(
        model,
        params,
        ServeConfig(
            batch_slots=args.slots, cache_len=args.cache_len, max_new_tokens=args.new_tokens
        ),
    )
    rng = np.random.default_rng(0)

    def submit(n: int) -> None:
        eng.submit(rng.integers(0, cfg.vocab_size, size=(n,)))

    tracer = None
    if args.trace != "off":
        tracer = Tracer(TraceConfig(out_dir=args.trace_dir, mode=args.trace)).start()
    try:
        t1 = time.perf_counter()
        for n in sorted(set(args.prompt_len)):
            submit(n)
        eng.run_until_drained()
        eng.completed.clear()
        t2 = time.perf_counter()
        for k in range(args.requests):
            submit(args.prompt_len[k % len(args.prompt_len)])
        done = eng.run_until_drained()
        t3 = time.perf_counter()
    finally:
        if tracer is not None:
            tracer.stop()
    tokens = [len(r.out_tokens) for r in done]
    print(
        f"served {len(done)} requests, {sum(tokens)} tokens: {cfg.name} "
        f"{cfg.num_layers} layers {cfg.dtype}, kernels={default_impl()}; "
        f"set-up {t2 - t0:.3f} s (init {t1 - t0:.3f} s, warm-up compiles {t2 - t1:.3f} s), "
        f"steady {t3 - t2:.3f} s ({sum(tokens) / (t3 - t2):.1f} tokens/s)"
    )
    if args.report:
        report = {
            "arch": cfg.name,
            "layers": cfg.num_layers,
            "dtype": cfg.dtype,
            "params": cfg.num_params(),
            "tokens_per_request": tokens,
            "init_s": t1 - t0,
            "warmup_s": t2 - t1,
            "steady_s": t3 - t2,
            "device": device_info(),
            "kernels": default_impl(),
            "device_memory": read_device_memory(),
        }
        with open(args.report, "w") as f:
            json.dump(report, f, indent=1)
    if tracer is not None:
        print(render(tally_trace(args.trace_dir), top=10))
    return 0


if __name__ == "__main__":
    sys.exit(main())
