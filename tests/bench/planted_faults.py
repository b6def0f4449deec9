"""The planted faults of ``test_hybrid.py`` at a cell's own size: one run
of the cell with each fault in place, printing the checks it reads.

    python3 tests/bench/planted_faults.py --workload granite-h-small-serve-chat --seed 7 --seconds 10

One JSON line per fault; every one should read ``"correct": false``.  The
CPU tests plant the same faults at the smoke size.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402
from tests.bench.test_hybrid import (  # noqa: E402
    _decode_ignores_cache,
    _moe_branch_unscaled,
    _no_routed_experts,
)

FAULTS = (_no_routed_experts, _decode_ignores_cache, _moe_branch_unscaled)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    for fault in FAULTS:
        with pytest.MonkeyPatch.context() as mp:
            fault(mp)
            r = run.run_cell(args.workload, args.seed, args.seconds, False)
        print(json.dumps({"fault": fault.__name__.strip("_"), "seed": args.seed,
                          "correct": r["correct"], "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
