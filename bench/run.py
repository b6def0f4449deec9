"""Run one benchmark cell once, on the chip this process finds.

    python bench/run.py --workload mamba2-serve-chat --seed 7 --seconds 30 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown`` of the profiler's trace, and last ``checks``:
each number compared with the plain reference beside its limit.  The same
checks are the last lines of standard error.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.

``--trace`` is the benchmark's own instrumentation (the JAX profiler over
the leading ``profile_seconds`` of the window, and the per-layer readers).
It is not the THAPI tracing mode: that is a parameter of the cell's traffic
file, on in both kinds of run, because the tracer is the system under test.

Everything is found by name, so a later change adds files and entries and
edits none:

- a configuration: ``bench/configs/<name>.json`` (sizes as run, ``source``,
  ``reduced``, ``assumed``), named by a ``configs`` entry of BENCHMARK.json;
- a traffic mix: ``bench/traffic/<name>.json``, parameters only; its
  ``kind`` names the generator that reads it, ``bench/kinds/<kind>.py``
  (``serve``: closed-loop clients on ``ServeEngine``);
- a cell: a ``workloads`` entry naming a configuration and a mix, and its
  limits in ``bench/limits/<cell>.json``, set from the readings that
  ``bench/control.py`` prints;
- a metric: an entry of ``end_to_end`` or ``per_layer`` and its reader
  ``bench/metrics/<name>.py``, whose ``read(ctx)`` returns a number, or None
  where the run gave it nothing to read (the metric is then left out).  The
  keys of ``ctx`` are listed in the generator's ``run``.

Every cell reports ``setup_s`` (process start to the window's start: weights,
compiles or the compile cache, warm-up and ramp).  JAX's persistent
compilation cache lives in ``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` names another.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(spec: dict, cell: str, trace: bool) -> list:
    """The metrics this cell reports in this kind of run."""
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m["workloads"] or ("workloads" not in m and m["moves"] in moved)]


def require_chips(chips: int, platform: str = "tpu") -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise SystemExit(f"JAX finds no {platform} (platform {devs[0].platform!r}); "
                         "the benchmark measures only on the chip")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX finds {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load_cell(spec: dict, workload: str):
    """(cell, configuration, traffic mix, limits, generator module) by name."""
    cell = find(spec["workloads"], workload, "workload")
    cfg = load_json(ROOT / find(spec["configs"], cell["config"], "config")["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{workload}.json")
    return cell, cfg, mix, limits, importlib.import_module(f"bench.kinds.{mix['kind']}")


def run_cell(workload: str, seed: int, seconds: float, trace: bool, platform: str = "tpu",
             spec: dict = None) -> dict:
    """One run of one cell; returns the result object.  ``platform`` and
    ``spec`` (in place of BENCHMARK.json) are for the CPU tests."""
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cell, cfg, mix, limits, kind = load_cell(spec, workload)
    device = require_chips(cell["chips"], platform)
    sys.path.insert(0, str(ROOT / "src"))
    compile_cache()
    from bench.peaks import peaks_for
    from bench.window import reduce_trace

    peaks = peaks_for(device["kind"]) if platform == "tpu" else None
    tmp = tempfile.mkdtemp(prefix="bench-")
    try:
        ctx = kind.run(cell, cfg, mix, seed, seconds, trace, limits, tmp, T_PROCESS, log)
        ctx["peaks"] = peaks
        if trace:
            reduce_trace(ctx, log)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    metrics = {}
    for m in metrics_of(spec, workload, trace):
        value = reader(m["name"])(ctx)
        if value is None and not trace:
            raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device["memory_peak_bytes"] = int(ctx["memory_peak_bytes"])
    result = {
        "correct": all(v <= lim for v, lim in ctx["checks"].values()),
        "attempted": int(ctx["requests"]),
        "failed": 0,  # a request that fails ends the run with no result
        "metrics": metrics,
        "device": device,
    }
    if trace:
        prof = ctx["profile"]
        device["busy_s"] = prof["busy_s"]
        device["window_s"] = prof["window_s"]
        result["breakdown"] = {"device_ops": prof["device_ops"], "idle_gaps": prof["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in ctx["checks"].items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # the TPU runtime logs to /tmp otherwise
    sys.path.insert(0, str(ROOT))
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except SystemExit as e:
        log(f"[bench] {e}")
        return 2
    except Exception:
        log(traceback.format_exc())
        return 1
    for k, c in result["checks"].items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
