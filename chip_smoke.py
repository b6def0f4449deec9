"""Bring-up smoke run of the traced serve and train path on a TPU.

    python chip_smoke.py             # one chip: device, kernels, serve, train, analysis
    python chip_smoke.py --chips 4   # four chips: data-parallel train vs one device

Everything runs in this one process (a chip belongs to one process) and
writes under ``chip_smoke_out/`` in the checkout.  Phases, in order:

1. device   — JAX must report a TPU; there is no CPU fallback.
2. kernels  — the three Pallas kernels at published widths against their
              jnp oracles (``kernels/ref.py``), each compiled program
              holding a ``tpu_custom_call``.
3. serve    — ``iprof run`` of ``repro.launch.serve`` on the whole published
              mamba2-1.3b (48 layers, bf16, random weights from seed 0).
4. train    — ``iprof run`` of ``repro.launch.train`` at full width with the
              depth cut to 16 layers (the full-depth optimizer state does
              not fit one chip's HBM).
5. analysis — the serve trace's fold tally equals the legacy-graph tally.

With ``--chips 4`` only the train phase runs: on a (4, 1) data mesh and on
one device with the same global batch, comparing the first losses.  The
last line of standard output is the JSON verdict; a failed phase exits
non-zero without printing it.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chip_smoke_out"

ARCH = "mamba2-1.3b"
TRAIN_ARGS = ["--arch", ARCH, "--layers", "16", "--seq", "1024", "--batch", "4", "--steps", "5",
              "--remat", "full"]
#: bf16 outputs: max |kernel - oracle| relative to max |oracle|
KERNEL_TOL = 2e-2
#: first losses of the 4-device and the 1-device run (bf16 params, f32 loss)
LOSS_TOL = 2e-2
LOSSES_COMPARED = 3


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        log(f"[chip_smoke] FAIL: {msg}")
        sys.exit(1)


def phase_device(chips: int) -> dict:
    from repro.core.telemetry import device_info
    from repro.jaxcompat import enable_compile_cache
    from repro.kernels.ops import default_impl

    dev = device_info()
    log(f"[device] {dev}")
    check(dev["platform"] == "tpu", f"JAX reports {dev['platform']!r}, not a TPU")
    check(dev["count"] >= chips, f"{chips} chips asked for, {dev['count']} present")
    impl = default_impl()
    log(f"[device] kernels={impl} (REPRO_KERNELS={os.environ.get('REPRO_KERNELS')!r})")
    check(impl == "pallas", "the jnp references are swapped in for the Pallas kernels")
    log(f"[device] compile cache: {enable_compile_cache()}")
    return dev


def _kernel_check(name: str, fn, args, ref, widths: str) -> None:
    import jax
    import numpy as np

    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(), f"{name}: no tpu_custom_call in the program")
    got = jax.tree_util.tree_leaves(compiled(*args))
    with jax.default_matmul_precision("highest"):
        want = jax.tree_util.tree_leaves(jax.jit(ref)(*args))
    for i, (g, w) in enumerate(zip(got, want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        check(g.shape == w.shape and np.isfinite(g).all(), f"{name}[{i}]: shape or non-finite")
        scale = float(np.abs(w).max()) or 1.0
        err = float(np.abs(g - w).max())
        log(f"[kernels] {name}[{i}] {widths}: max abs err {err} "
            f"(max |ref| {scale}, rel {err / scale}, tol {KERNEL_TOL}) tpu_custom_call=yes")
        check(err / scale <= KERNEL_TOL, f"{name}[{i}] off its reference")


def phase_kernels() -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.rglru_scan import rglru_pallas
    from repro.kernels.ssd_scan import ssd_pallas

    rng = np.random.default_rng(0)

    def normal(*shape, scale=1.0, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    # mamba2-1.3b: H 64, P 64, N 128, G 1, chunk 256
    B, S, H, P, G, N = 2, 512, 64, 64, 1, 128
    ssd_args = (
        normal(B, S, H, P),
        jnp.asarray(rng.uniform(1e-3, 0.1, (B, S, H)), jnp.float32),
        jnp.asarray(rng.uniform(0.0, 2.0, (H,)), jnp.float32),
        normal(B, S, G, N, scale=N**-0.5),
        normal(B, S, G, N, scale=N**-0.5),
        normal(H, dtype=jnp.float32),
    )
    _kernel_check(
        "ssd_scan",
        lambda *a: ssd_pallas(*a, chunk=256),
        ssd_args,
        lambda *a: ref.ssd_ref(*a, chunk=256),
        f"B{B} S{S} H{H} P{P} G{G} N{N} chunk 256 bf16",
    )
    # recurrentgemma-2b: lru width C 2560
    B, S, C = 2, 512, 2560
    rg_args = (normal(B, S, C), normal(B, S, C), normal(B, S, C), normal(C, dtype=jnp.float32),
               normal(B, C, dtype=jnp.float32))
    _kernel_check("rglru_scan", rglru_pallas, rg_args, ref.rglru_ref, f"B{B} S{S} C{C} bf16")
    # h2o-danube-1.8b: 32 heads, 8 kv heads, head dim 80; window cut to 512
    # so that it masks inside S 2048
    B, S, H, Kv, hd, win = 1, 2048, 32, 8, 80, 512
    fa_args = (normal(B, S, H, hd), normal(B, S, Kv, hd), normal(B, S, Kv, hd))
    _kernel_check(
        "flash_attention",
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True, window=win),
        fa_args,
        lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True, window=win),
        f"B{B} S{S} H{H} Kv{Kv} hd{hd} window {win} bf16",
    )


def _tally(trace_dir: Path, rows) -> object:
    from repro.core.plugins.tally import tally_trace

    t = tally_trace(str(trace_dir))
    names = {api for _, api in t.apis}
    for row in rows:
        check(row in names, f"no {row} row in the tally of {trace_dir.name}: {sorted(names)}")
    check(t.discarded == 0, f"{trace_dir.name}: {t.discarded} events dropped")
    return t


def _gib(devices_memory) -> str:
    return ", ".join(f"dev{i} peak {p / 2**30:.3f} GiB of {lim / 2**30:.3f}"
                     for i, (_, p, lim) in enumerate(devices_memory))


def _iprof_run(name: str, entry: str, argv) -> dict:
    from repro.core.iprof import main as iprof

    trace_dir, report = OUT / name, OUT / f"{name}.json"
    rc = iprof(["run", "-m", "default", "-o", str(trace_dir), entry, "--",
                *argv, "--report", str(report)])
    check(rc == 0, f"{name}: {entry} returned {rc}")
    gc.collect()
    return json.loads(report.read_text())


def phase_serve() -> Path:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import Model

    rep = _iprof_run("serve", "repro.launch.serve:main", [
        "--arch", ARCH, "--requests", "8", "--prompt-len", "256", "512", "--new-tokens", "32",
        "--slots", "4", "--cache-len", "1024"])
    check(rep["layers"] == 48 and rep["dtype"] == "bfloat16", f"serve ran {rep['layers']} layers {rep['dtype']}")
    check(rep["tokens_per_request"] == [32] * 8, f"tokens per request {rep['tokens_per_request']}")
    _tally(OUT / "serve", ("prefill", "decode_step", "dispatch", "block_until_ready"))
    model = Model(get_config(ARCH))
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    text = jax.jit(lambda p, b: model.prefill(p, b, 1024)).lower(model.shapes(), {"tokens": tokens}).as_text()
    check("tpu_custom_call" in text and "ssd_scan" in text, "the prefill program holds no SSD kernel")
    log(f"[serve] {rep['arch']} {rep['layers']} layers {rep['dtype']} ({rep['params']} params): "
        f"8 requests × 32 tokens; set-up {rep['init_s'] + rep['warmup_s']} s "
        f"(init {rep['init_s']} s, warm-up compiles {rep['warmup_s']} s), steady {rep['steady_s']} s; "
        f"prefill program holds ssd_scan; {_gib(rep['device_memory'])}")
    return OUT / "serve"


def _train(name: str, devices: int) -> dict:
    rep = _iprof_run(name, "repro.launch.train:main", [*TRAIN_ARGS, "--devices", str(devices)])
    check(rep["steps_run"] == 5, f"{name}: {rep['steps_run']} steps run")
    check(rep["failures"] == 0, f"{name}: {rep['failures']} trainer retries")
    check(all(math.isfinite(v) for v in rep["losses"]), f"{name}: losses {rep['losses']}")
    _tally(OUT / name, ("train_step",))
    log(f"[{name}] {rep['arch']} {rep['layers']} layers on {rep['devices']} device(s): "
        f"losses {rep['losses']}; step times {rep['step_s']} s (first includes compile); "
        f"failures 0; {_gib(rep['device_memory'])}")
    return rep


def phase_analysis(trace_dir: Path) -> None:
    from repro.core.plugins.tally import tally_trace

    def canon(t):
        o = t.to_obj()
        o["apis"], o["device_apis"] = sorted(o["apis"]), sorted(o["device_apis"])
        return o

    check(canon(tally_trace(str(trace_dir))) == canon(tally_trace(str(trace_dir), legacy_graph=True)),
          "fold tally differs from the legacy-graph tally")
    log("[analysis] serve trace: fold tally == legacy-graph tally")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        check(False, f"the program is not here ({e}): run from a checkout of the repository")
    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)

    dev = phase_device(args.chips)
    if args.chips == 4:
        four, one = _train("train_4chips", 4), _train("train_1chip", 1)
        diffs = [abs(a - b) for a, b in zip(four["losses"], one["losses"])][:LOSSES_COMPARED]
        log(f"[train] first {LOSSES_COMPARED} losses, 4 chips {four['losses'][:LOSSES_COMPARED]} "
            f"vs 1 chip {one['losses'][:LOSSES_COMPARED]}: |diff| {diffs} (tol {LOSS_TOL})")
        check(max(diffs) <= LOSS_TOL, "4-chip losses differ from the 1-chip run")
    else:
        phase_kernels()
        serve_dir = phase_serve()
        _train("train", 1)
        phase_analysis(serve_dir)
    print(json.dumps({"ok": True, "device": {"platform": dev["platform"], "kind": dev["kind"],
                                             "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
