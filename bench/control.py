"""Readings that a cell's limits are set from, at the cell's own size.

    python bench/control.py --workload mamba2-serve-chat --seeds 11 12 13 --seconds 8

For every seed, in this one process: a run of the cell with a short window
at the cell's own load (long enough to finish the mix's longest requests),
the numbers it compares with the reference, and the same numbers for the
control (the reference computed with fp8 matrix products, put in the
program's place).  One JSON line per seed, then a summary per number: the
largest program reading (the lower end of a limit) and the smallest reading
of the control (its upper end).  The benchmark's own runs never run the
control.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # the TPU runtime logs to /tmp otherwise
    sys.path.insert(0, str(ROOT))
    from bench import run

    cell, cfg, mix, limits, kind = run.load_cell(run.load_json(ROOT / "BENCHMARK.json"), args.workload)
    run.require_chips(cell["chips"])
    sys.path.insert(0, str(ROOT / "src"))
    run.compile_cache()
    readings = []
    for seed in args.seeds:
        tmp = tempfile.mkdtemp(prefix="bench-control-")
        try:
            ctx = kind.run(cell, cfg, mix, seed, args.seconds, False, limits, tmp,
                           time.perf_counter(), run.log, control=True)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        r = {"seed": seed, "program": {k: v for k, (v, _) in ctx["checks"].items()},
             "control": ctx["control"]}
        readings.append(r)
        print(json.dumps(r), flush=True)
        del ctx
        gc.collect()
    summary = {"workload": args.workload, "seeds": args.seeds}
    for k in readings[0]["program"]:
        summary[k] = {"program_max": max(r["program"][k] for r in readings)}
        if k in readings[0]["control"]:
            summary[k]["control_min"] = min(r["control"][k] for r in readings)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
