"""Serving cells of a Granite-4.0-H configuration (family ``mamba_hybrid``):
the closed loop of ``bench/kinds/serve.py`` with this family's weights,
counts and reference.

Reads the same traffic parameters as ``serve`` (its docstring lists them),
with ``kind`` ``serve_hybrid``.  Set-up, ramp and window are those of
``serve``; what differs is the model: the weights and program
configuration (``bench/weights_hybrid.py``), the FLOPs (``bench/
flops_hybrid.py``), the comparison with ``bench/reference_hybrid.py``, and
what the THAPI trace adds: the ``moe_route`` pair of every prefill and
decode step, read back from the trace (held experts' pairs and largest
load) and counted among the calls THAPI must report.

``run`` repeats ``serve.run``'s warm-up, ramp, window loop and token and
TTFT accounting line for line, so that this cell's ``serve_tok_s`` and
``ttft_p95_ms`` mean what the Mamba2 cells' do: a change to how ``serve``
times the window or counts tokens has to be made here too, until one
generator picks the model's lines by family (PERF.md, Open questions).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench.kinds.serve import request_pool
from bench.window import Window, device_peak_bytes, span_seconds, unreported_calls


def route_counts(trace_dir: str) -> dict:
    """Sums over the trace's ``moe_route`` pairs, by phase: pairs, largest
    loads, mean loads (pairs ÷ held), and records."""
    from repro.core.babeltrace import intervals_of

    ivs, _, _ = intervals_of(trace_dir)
    out = {ph: {"pairs": 0, "max_load": 0, "mean_load": 0.0, "records": 0} for ph in ("prefill", "decode")}
    for iv in ivs:
        if iv.api == "moe_route" and iv.exit is not None:
            o = out["decode" if iv.entry["decode"] else "prefill"]
            o["pairs"] += iv.exit["pairs"]
            o["max_load"] += iv.exit["max_load"]
            o["mean_load"] += iv.exit["pairs"] / iv.entry["held"]
            o["records"] += 1
    return out


def run(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float, trace: bool,
        limits: dict, tmp: str, t_process: float, log, control: bool = False) -> dict:
    """One run, as ``serve.run``; returns the ``ctx`` that the metric
    readers take, with ``attn_calls`` and ``route`` beside serve's keys."""
    import jax

    from bench import flops_hybrid as flops
    from bench.weights_hybrid import dims, make_weights, program_config
    from repro.models import Model
    from repro.serve import ServeConfig, ServeEngine

    win = Window(seconds, trace, mix, tmp, t_process)
    z = dims(cfg)
    out_tokens = mix["output_tokens"]
    model = Model(program_config(cfg, z))
    params = make_weights(cfg, seed, cfg["torch_dtype"])
    want = jax.tree_util.tree_map(lambda s: (s.shape, str(s.dtype)), model.shapes())
    got = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), params)
    if want != got:
        raise RuntimeError(f"the program's parameter layout changed: {want} != {got}")
    eng = ServeEngine(model, params, ServeConfig(
        batch_slots=mix["slots"], cache_len=max(mix["prompt_buckets"]) + out_tokens, max_new_tokens=out_tokens))
    del params
    pool = request_pool(mix, z["V"], seed)

    # warm-up: one request per length, stopping at its first token
    eng.cfg.max_new_tokens = 1
    for n in sorted({len(p) for p in pool}):
        eng.submit(pool[0][:1].repeat(n))
    eng.run_until_drained()
    eng.completed.clear()
    eng.cfg.max_new_tokens = out_tokens

    sent = {}  # rid → (client, submit time, request)
    waiting = set()
    nxt = 0

    def submit(client: int):
        nonlocal nxt
        r = eng.submit(pool[nxt % len(pool)])
        sent[r.rid] = (client, time.perf_counter(), r)
        waiting.add(r.rid)
        nxt += 1

    starts: dict = {}
    for k in range(mix["clients"]):
        starts.setdefault(int(k * out_tokens / mix["clients"]), []).append(k)
    seen = 0
    for i in range(out_tokens):
        for k in starts.get(i, []):
            submit(k)
        eng.step()
        for r in eng.completed[seen:]:
            submit(sent[r.rid][0])
        seen = len(eng.completed)

    at_start = {rid: len(r.out_tokens) for rid, (_, _, r) in sent.items()}
    waiting.difference_update(rid for rid, n in at_start.items() if n)
    ttft, served, prof_prefills, steps = [], [], [], 0
    step_s = []
    gc.collect()
    gc.freeze()
    win.start()
    while True:
        t0 = time.perf_counter()
        with win.span("engine.step"):
            eng.step()
        steps += 1
        t = time.perf_counter()
        step_s.append((t - t0, len(served)))
        for rid in [rid for rid in waiting if sent[rid][2].out_tokens]:
            waiting.discard(rid)
            ttft.append(t - sent[rid][1])
            served.append(rid)
            if win.profiling:
                prof_prefills.append(len(sent[rid][2].prompt))
        if not win.tick():
            break
        with win.span("submit"):
            for r in eng.completed[seen:]:
                submit(sent[r.rid][0])
            seen = len(eng.completed)
    handle, tally, fold_s = win.close()
    gc.unfreeze()
    route = route_counts(handle.trace_dir)

    mem_peak = device_peak_bytes()
    tokens = sum(len(r.out_tokens) - at_start.get(rid, 0) for rid, (_, _, r) in sent.items())
    finished = [sent[rid][2] for rid in served if sent[rid][2].done]
    short = sum(1 for r in finished if len(r.out_tokens) != out_tokens)
    made = {"prefill": len(served), "decode_step": steps, "dispatch": len(served) + steps,
            "moe_route": len(served) + steps}
    unreported, tallied = unreported_calls(made, tally, handle)
    prompts = [len(sent[rid][2].prompt) for rid in served]
    prefill_flops = sum(flops.prefill_flops(n, z) for n in prompts)
    # decode attention at the pool's mean context, half-way through a request
    context = float(np.mean([len(p) for p in pool])) + out_tokens / 2
    ctx = {
        "setup_s": win.setup_s,
        "window_s": win.window_s,
        "tokens_out": tokens,
        "ttft_s": ttft,
        "requests": len(served),
        "model_flops": prefill_flops + (tokens - len(served)) * flops.decode_flops_per_token(z, int(context)),
        "prefill_flops": prefill_flops,
        "prefill_span_s": span_seconds(tally, "prefill"),
        "thapi": {"events": handle.events, "dropped": handle.dropped},
        "profile_ev_s": handle.events / fold_s,
        "memory_peak_bytes": mem_peak,
        "dims": z,
        "ssd_calls": [(n, z["Lm"]) for n in prof_prefills],
        "attn_calls": [(n, z["La"]) for n in prof_prefills],
        "route": route,
        "profile_dir": win.profile_dir if trace else None,
    }
    admitted = np.diff([n for _, n in step_s] + [len(served)])
    took = np.asarray([d for d, _ in step_s])
    log(f"[serve] steps: decode-only median {1e3 * np.median(took[admitted == 0]) if (admitted == 0).any() else 0:.2f} ms, "
        f"with admissions median {1e3 * np.median(took[admitted > 0]) if (admitted > 0).any() else 0:.2f} ms, "
        f"longest {1e3 * took.max():.2f} ms; {sum(prompts)} prompt tokens prefilled")
    log(f"[serve] {cell['name']}: {len(served)} requests first served, {tokens} tokens, "
        f"{steps} engine steps in {win.window_s:.3f} s; set-up {win.setup_s:.3f} s; compiles in "
        f"window {win.compiles_in_window}; THAPI {mix['thapi_mode']}: {handle.events} events, "
        f"{handle.dropped} dropped, tally in {fold_s} s; calls made {made} tallied {tallied}; route {route}")

    # -- correctness: the served tokens against the plain reference ----------
    if not finished:
        raise RuntimeError("no request finished in the window")
    rng = np.random.default_rng(seed % (1 << 63))
    longest = max(range(len(finished)), key=lambda i: len(finished[i].prompt))
    others = [i for i in range(len(finished)) if i != longest]
    pick = [longest] + rng.choice(others, size=min(len(others), mix["check_rows"] - 1), replace=False).tolist()
    rows = [(finished[i].prompt, np.asarray(finished[i].out_tokens, np.int32)) for i in pick]
    del eng, finished, sent, win
    gc.collect()
    log(f"[serve] live device bytes before the reference: {sum(a.nbytes for a in jax.live_arrays())}")
    gaps = check_rows(cfg, seed, rows, control)
    ctx["checks"] = {
        "logit_gap": (float(gaps["served"].max()), limits["logit_gap"]),
        "short_requests": (short, 0),
        "profile_unreported_calls": (unreported, 0),
    }
    if control:
        ctx["control"] = {"logit_gap": float(gaps["control"].max())}
    ctx["served_tokens_checked"] = int(sum(len(o) for _, o in rows))
    return ctx


def check_rows(cfg: dict, seed: int, rows, control: bool = False) -> dict:
    """Gaps of the served tokens of ``rows`` ([(prompt, served)]) under the
    reference, padded to one length so one program serves every run."""
    import jax.numpy as jnp

    from bench import reference_hybrid as reference
    from bench.weights_hybrid import dims, make_weights

    z = dims(cfg)
    M = max(len(o) for _, o in rows)
    m = reference.ROW_MULTIPLE
    S = -(-max(len(p) + len(o) - 1 for p, o in rows) // m) * m
    toks = np.zeros((len(rows), S), np.int32)
    pos = np.zeros((len(rows), M), np.int32)
    served = np.zeros((len(rows), M), np.int32)
    for i, (p, o) in enumerate(rows):
        seq = np.concatenate([p, o[:-1]])
        toks[i, : len(seq)] = seq
        pos[i] = len(p) - 1 + np.arange(M)
        served[i] = o
    w = make_weights(cfg, seed, cfg["torch_dtype"])  # held as bfloat16, upcast per layer
    out = reference.logit_gaps(z, w, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(served), control)
    return {k: np.asarray(v) for k, v in out.items()}
