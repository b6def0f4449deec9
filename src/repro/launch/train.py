"""Training launcher: ``--arch <id>`` selects an assigned architecture.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-1.3b --layers 16 \
        --seq 1024 --batch 4 --steps 5 --remat full

Trains the published config at its published widths with random weights
from seed 0, data-parallel over a ``(devices, 1)`` mesh of the local
devices.  ``--layers N`` cuts depth only (for a model whose full-depth
state does not fit the devices); ``--smoke`` picks the reduced same-family
config instead.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.core import TraceConfig, Tracer
from repro.core.plugins.tally import render, tally_trace
from repro.core.telemetry import device_info, read_device_memory
from repro.jaxcompat import device_mesh, enable_compile_cache
from repro.kernels.ops import default_impl
from repro.models import Model, ShapeSpec
from repro.sharding import Partitioner
from repro.train import TrainConfig, Trainer, TrainerConfig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCHS)
    ap.add_argument("--smoke", action="store_true", help="reduced same-family config")
    ap.add_argument("--layers", type=int, default=None, help="cut depth to N layers")
    ap.add_argument("--remat", choices=["none", "dots", "full"], default=None)
    ap.add_argument("--devices", type=int, default=None, help="data-parallel over the first N")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--report", default=None, help="write a JSON summary here")
    ap.add_argument("--trace", choices=["off", "minimal", "default", "full"], default="off")
    ap.add_argument("--sample", action="store_true")
    ap.add_argument("--trace-dir", default="/tmp/thapi_train")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.layers is not None and args.layers != cfg.num_layers:
        print(f"[train] {cfg.name}: depth cut {cfg.num_layers} → {args.layers} layers, widths unchanged")
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    if args.remat is not None:
        cfg = dataclasses.replace(cfg, remat=args.remat)

    n = args.devices or len(jax.devices())
    mesh = device_mesh(np.array(jax.devices()[:n]).reshape(n, 1), ("data", "model"))
    model = Model(cfg, mesh)
    shape = ShapeSpec("cli", "train", args.seq, args.batch)
    trainer = Trainer(
        model,
        shape,
        Partitioner(mesh, fsdp=cfg.fsdp),
        TrainConfig(
            peak_lr=args.lr,
            warmup=max(2, args.steps // 10),
            total_steps=max(args.steps, 10),
            microbatches=args.microbatches,
            grad_compression=args.grad_compression,
        ),
        TrainerConfig(steps=args.steps, ckpt_every=max(args.steps // 2, 1), ckpt_dir=args.ckpt_dir),
    )
    tracer = None
    if args.trace != "off":
        tracer = Tracer(
            TraceConfig(out_dir=args.trace_dir, mode=args.trace, sample=args.sample)
        ).start()
    try:
        res = trainer.run()
    finally:
        if tracer is not None:
            tracer.stop()
    h = res["history"]
    times = [e["time_s"] for e in h]
    print(
        f"{cfg.name} ({cfg.num_layers} layers, {n} device(s), kernels={default_impl()}): "
        f"loss {h[0]['loss']:.4f} → {h[-1]['loss']:.4f} in {res['steps_run']} steps, "
        f"{res['failures']} failures; first step {times[0]:.3f} s (compile), "
        f"steady {np.median(times[1:]) if len(times) > 1 else math.nan:.3f} s/step"
    )
    if args.report:
        report = {
            "arch": cfg.name,
            "layers": cfg.num_layers,
            "devices": n,
            "steps_run": res["steps_run"],
            "failures": res["failures"],
            "losses": [e["loss"] for e in h],
            "step_s": times,
            "device": device_info(),
            "kernels": default_impl(),
            "device_memory": read_device_memory(),
        }
        with open(args.report, "w") as f:
            json.dump(report, f, indent=1)
    if tracer is not None:
        print(render(tally_trace(args.trace_dir), top=8))
    return 0


if __name__ == "__main__":
    sys.exit(main())
