import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimb driver — the three chosen cells, hypothesis → change →
measure → validate (methodology in EXPERIMENTS.md §Perf).

Cells (from the §Roofline baseline table):
  A qwen1.5-32b × train_4k      — worst useful-flops ratio among train cells
                                  (0.18): 40 heads don't divide the 16-way
                                  model axis → attention entirely unsharded.
  B kimi-k2-1t-a32b × decode_32k — most collective-bound cell (7.8 s vs
                                  2.2 s memory): FSDP re-gathers 1T of expert
                                  weights every decode step.
  C mistral-large-123b × train_4k — most representative production cell
                                  (flagship dense train; best baseline 18%).

    PYTHONPATH=src python -m repro.launch.perf --cell all -o results/perf
"""

import argparse
import dataclasses
import json
import sys
from typing import Callable, Dict, List, Optional

from repro.configs import get_config
from repro.launch.dryrun import run_cell


def _v(name: str, hypothesis: str, prediction: str, transform: Callable, **extra):
    return dict(
        name=name, hypothesis=hypothesis, prediction=prediction, transform=transform, **extra
    )


CELLS: Dict[str, dict] = {
    "A": {
        "arch": "qwen1.5-32b",
        "shape": "train_4k",
        "variants": [
            _v(
                "headpad16",
                "40 q/kv heads % 16 ≠ 0 ⇒ attention weights+activations are "
                "replicated over the model axis; every device computes all 40 "
                "heads and materializes full [S,S] scores. Padding heads to 48 "
                "(3/device) shards attention 16-ways.",
                "memory term ≈ ÷10 (score bytes 43 TB→3.2 TB/device ×bwd); "
                "compute term ↓ similarly; roofline fraction 2.7% → >15%",
                lambda c: dataclasses.replace(c, head_pad_to=16),
            ),
            _v(
                "headpad16+chunked",
                "Dense attention materializes [S,S] f32 scores in several "
                "passes (softmax, mask, bwd). A KV-block online-softmax scan "
                "(flash-style) keeps only [S,chunk] alive.",
                "memory term ↓ further ~1.5–2×; compute ~flat",
                lambda c: dataclasses.replace(
                    c, head_pad_to=16, attn_impl="chunked", attn_chunk=1024
                ),
            ),
        ],
    },
    "B": {
        "arch": "kimi-k2-1t-a32b",
        "shape": "decode_32k",
        "variants": [
            _v(
                "serve2d",
                "FSDP shards 2 TB of expert weights over (data×model) and "
                "all-gathers them EVERY decode step (~390 GB/device of "
                "collectives for 128 tokens). Keeping weights resident in a "
                "2D layout (experts×model, expert-FFN×data) and moving the "
                "1.8 MB of activations instead inverts the ratio.",
                "collective term 7.8 s → <0.05 s; memory term becomes the "
                "weight-read bound (~8 GB/device ⇒ ~10 ms); bound flips to "
                "memory, roofline fraction ≫ baseline",
                lambda c: dataclasses.replace(c, serve_2d=True),
            ),
        ],
    },
    "C": {
        "arch": "mistral-large-123b",
        "shape": "train_4k",
        "variants": [
            _v(
                "chunked",
                "96 heads / 16 = 6/device are already TP-sharded, but dense "
                "attention still materializes [S,S] f32 scores per head "
                "(16×6×4096²×4 B ≈ 6.4 TB/device per pass). Chunked online "
                "softmax removes the full materialization.",
                "memory term 83 s → ~55 s; compute flat; fraction 18% → ~27%",
                lambda c: dataclasses.replace(c, attn_impl="chunked", attn_chunk=1024),
            ),
            _v(
                "chunked+remat_micro8",
                "Baseline peak HBM 860 GB/device ⇒ doesn't fit 16 GB. Full "
                "remat + 8 microbatches cuts live activations ~8× at ~+33% "
                "recompute FLOPs — fit is a hard constraint at this scale.",
                "peak_bytes ≈ ÷8–20 (toward fitting); compute term +≤33%; "
                "memory term similar or ↓ (smaller live set)",
                lambda c: dataclasses.replace(
                    c, attn_impl="chunked", attn_chunk=1024, remat="full"
                ),
                microbatches=8,
            ),
        ],
    },
}


def run_cell_variants(cell_key: str, out_dir: Optional[str]) -> List[dict]:
    cell = CELLS[cell_key]
    arch, shape = cell["arch"], cell["shape"]
    results = []
    base_cfg = get_config(arch)
    print(f"=== cell {cell_key}: {arch} × {shape} ===")
    base = run_cell(arch, shape, multi_pod=False, cfg_override=base_cfg)
    base["variant"] = "baseline"
    results.append(base)
    for v in cell["variants"]:
        print(f"\n--- variant {v['name']} ---")
        print("hypothesis:", v["hypothesis"])
        print("prediction:", v["prediction"])
        cfg = v["transform"](base_cfg)
        res = run_cell(
            arch,
            shape,
            multi_pod=False,
            cfg_override=cfg,
            microbatches=v.get("microbatches", 1),
        )
        res["variant"] = v["name"]
        res["hypothesis"] = v["hypothesis"]
        res["prediction"] = v["prediction"]
        b, n = base["roofline"], res["roofline"]
        res["delta"] = {
            "t_compute": n["t_compute"] / max(b["t_compute"], 1e-12),
            "t_memory": n["t_memory"] / max(b["t_memory"], 1e-12),
            "t_collective": n["t_collective"] / max(b["t_collective"], 1e-12),
            "roofline_fraction": n["roofline_fraction"] / max(b["roofline_fraction"], 1e-12),
            "peak_bytes": n.get("peak_bytes", 0) / max(b.get("peak_bytes", 1), 1),
        }
        print("delta vs baseline:", {k: round(x, 3) for k, x in res["delta"].items()})
        results.append(res)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"cell_{cell_key}.json"), "w") as f:
            json.dump(results, f, indent=1)
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="all", choices=["all", "A", "B", "C"])
    ap.add_argument("-o", "--out", default="results/perf")
    args = ap.parse_args(argv)
    keys = list(CELLS) if args.cell == "all" else [args.cell]
    for k in keys:
        run_cell_variants(k, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
