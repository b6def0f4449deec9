import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import (jax locks the platform and device count
# on first init).  The dry-run is a CPU-lowering tool: it never touches an
# accelerator, so it can run beside a process that holds the chip.  512
# placeholder host devices back the 2×16×16 production mesh; the dry-run
# lowers + compiles every (arch × shape × mesh) cell with ShapeDtypeStructs —
# no arrays are ever allocated.

"""Multi-pod dry-run (assignment deliverable e).

For every (architecture × its applicable shapes) × {single-pod 16×16,
multi-pod 2×16×16}:

    with mesh:
        lowered = jax.jit(step, in_shardings=…, out_shardings=…).lower(**specs)
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline

Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the system.  Results land as JSON in --out for EXPERIMENTS.md
§Dry-run/§Roofline and benchmarks/roofline.py.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both -o results/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --arch kimi-k2-1t-a32b --shape train_4k --mesh single
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import List, Optional, Tuple

import jax

from repro.configs import ARCHS, get_config
from repro.launch.mesh import describe, make_production_mesh
from repro.models import Model, SHAPES, applicable_shapes
from repro.sharding import Partitioner


def cells(arch_filter: str, shape_filter: str, mesh_filter: str) -> List[Tuple[str, str, bool]]:
    out = []
    archs = ARCHS if arch_filter == "all" else [arch_filter]
    for arch in archs:
        cfg = get_config(arch)
        for shape in applicable_shapes(cfg):
            if shape_filter != "all" and shape.name != shape_filter:
                continue
            for multi in (False, True):
                if mesh_filter == "single" and multi:
                    continue
                if mesh_filter == "multi" and not multi:
                    continue
                out.append((arch, shape.name, multi))
    return out


def _lower_and_compile(cfg, shape, mesh, part, microbatches: int = 1):
    """Lower + compile the production step for one (cfg, shape, mesh)."""
    model = Model(cfg, mesh)
    with mesh:
        if shape.kind == "train":
            from repro.train.train_step import TrainConfig, build_train_artifacts

            tcfg = TrainConfig(adamw=_adamw_for(cfg), microbatches=microbatches)
            step, state_shapes, _, batch_shapes, _ = build_train_artifacts(
                model, part, shape, tcfg
            )
            lowered = step.jit.lower(state_shapes, batch_shapes)
        elif shape.kind == "prefill":
            from repro.serve.artifacts import prefill_artifacts

            fn, shapes, _ = prefill_artifacts(model, part, shape)
            lowered = fn.lower(*shapes)
        else:
            from repro.serve.artifacts import decode_artifacts

            fn, shapes, _ = decode_artifacts(model, part, shape)
            lowered = fn.lower(*shapes)
        return lowered, lowered.compile()


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    verbose: bool = True,
    cfg_override=None,
    extrapolate_depth: bool = True,
    microbatches: int = 1,
) -> dict:
    from repro.launch import roofline as rl
    from repro.models.config import depth_units, with_depth

    cfg = cfg_override if cfg_override is not None else get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    if cfg.serve_2d and shape.kind == "decode":
        part = Partitioner(mesh, mode="serve2d")  # resident 2D expert weights
    else:
        part = Partitioner(mesh, fsdp=cfg.fsdp)
    t0 = time.monotonic()
    # 1) full-depth compile: THE proof that the production step lowers,
    #    shards and fits (memory analysis) on this mesh.
    lowered, compiled = _lower_and_compile(cfg, shape, mesh, part, microbatches)
    t_compile = time.monotonic() - t0
    mem_stats = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if verbose:
        print(f"--- {arch} × {shape_name} × {describe(mesh)} ---")
        print("memory_analysis:", mem_stats)
        print(
            "cost_analysis:",
            {k: cost[k] for k in ("flops", "bytes accessed") if k in cost},
        )
    # 2) roofline terms: unrolled 1-unit / 2-unit depth compiles +
    #    linear extrapolation (XLA cost analysis counts loop bodies once).
    units = depth_units(cfg)
    if extrapolate_depth and units >= 2:
        _, c1 = _lower_and_compile(with_depth(cfg, 1), shape, mesh, part, microbatches)
        _, c2 = _lower_and_compile(with_depth(cfg, 2), shape, mesh, part, microbatches)
        meas = rl.extrapolate(rl.measure(c1), rl.measure(c2), units)
    else:
        meas = rl.measure(compiled)
    rf = rl.roofline_from(
        meas,
        rl.model_flops_for(cfg, shape, mesh.size),
        rl.memory_stats(compiled),
    )
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": describe(mesh),
        "multi_pod": multi_pod,
        "n_devices": mesh.size,
        "ok": True,
        "t_compile_s": round(t_compile, 2),
        "t_total_s": round(time.monotonic() - t0, 2),
        "scan_body_once_flops": float(cost.get("flops", 0.0)),
        "roofline": rf.to_json(),
    }
    if verbose:
        print(
            f"terms: compute={rf.t_compute:.4f}s memory={rf.t_memory:.4f}s "
            f"collective={rf.t_collective:.4f}s → {rf.bottleneck}-bound; "
            f"MODEL/HLO flops={rf.useful_flops_ratio:.3f} "
            f"roofline_fraction={rf.roofline_fraction:.3f}"
        )
    return result


def _adamw_for(cfg):
    from repro.optim import AdamWConfig

    # 1T-param config: bf16 optimizer state to approach the HBM budget
    return AdamWConfig(state_dtype="bfloat16" if cfg.fsdp else "float32")


def _result_path(out_dir: str, arch: str, shape: str, multi: bool) -> str:
    mesh = "pod2x16x16" if multi else "pod16x16"
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh}.json")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", choices=["all"] + ARCHS)
    ap.add_argument("--shape", default="all", choices=["all"] + list(SHAPES))
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("-o", "--out", default=None, help="write per-cell JSON here")
    ap.add_argument("--jobs", type=int, default=1, help="parallel worker processes")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    todo = cells(args.arch, args.shape, args.mesh)
    if args.list:
        for c in todo:
            print(*c)
        return 0
    if args.out:
        os.makedirs(args.out, exist_ok=True)

    if args.jobs > 1:
        return _parallel(todo, args)

    failures = 0
    for arch, shape, multi in todo:
        path = _result_path(args.out, arch, shape, multi) if args.out else None
        if path and args.skip_existing and os.path.exists(path):
            continue
        try:
            # roofline extrapolation only on the single-pod mesh (the
            # §Roofline table is single-pod; multi-pod is the compile proof)
            res = run_cell(arch, shape, multi, extrapolate_depth=not multi)
        except Exception as e:  # a failing cell is a bug — record it loudly
            traceback.print_exc()
            res = {
                "arch": arch,
                "shape": shape,
                "multi_pod": multi,
                "ok": False,
                "error": f"{type(e).__name__}: {e}",
            }
            failures += 1
        if path:
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
    print(f"dry-run: {len(todo) - failures}/{len(todo)} cells compiled")
    return 1 if failures else 0


def _parallel(todo, args) -> int:
    """Spawn one subprocess per cell (compile isolation + parallelism)."""
    pending = []
    failures = 0
    idx = 0
    done = 0
    while done < len(todo):
        while len(pending) < args.jobs and idx < len(todo):
            arch, shape, multi = todo[idx]
            idx += 1
            path = _result_path(args.out, arch, shape, multi) if args.out else None
            if path and args.skip_existing and os.path.exists(path):
                done += 1
                continue
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", shape,
                "--mesh", "multi" if multi else "single",
            ]
            if args.out:
                cmd += ["-o", args.out]
            p = subprocess.Popen(cmd)
            pending.append(((arch, shape, multi), p))
        time.sleep(0.5)
        still = []
        for cell, p in pending:
            if p.poll() is None:
                still.append((cell, p))
            else:
                done += 1
                if p.returncode != 0:
                    failures += 1
                    print(f"[dryrun] FAILED: {cell}")
        pending = still
    print(f"dry-run: {len(todo) - failures}/{len(todo)} cells compiled")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
