"""Serving cells: a closed loop of clients on the engine's slots.

Reads a traffic file of kind ``serve``:

    clients, slots        clients in the loop; decode slots of the engine
    prompt_lognormal      {"median", "sigma"} of the prompt length, in tokens
    prompt_buckets        prompt lengths that exist; a drawn length is
                          rounded up to the next one (the last caps it)
    output_tokens         tokens served per request (the engine stops every
                          request at one ``max_new_tokens``)
    block, pool_blocks    every block of ``block`` requests holds the same
                          stratified set of lengths, in an order drawn from
                          the seed; the pool holds ``pool_blocks`` blocks
    thapi_mode            THAPI tracing mode of the session around the window
    check_rows            finished requests compared with the reference
    profile_seconds       leading part of the window the profiler records
                          in a ``--trace 1`` run

Each client sends its next request as soon as its last one completes.  Set-up
makes the weights from the seed, compiles (or loads from the cache) one
prefill program per bucket the mix draws and the decode step, each run once,
and ramps the clients in, staggered so that completions spread evenly over
the decode steps.  The harness's own objects are then frozen out of the
garbage collector's scans, and the window runs ``ServeEngine.step`` for
``seconds``.
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from bench.window import Window, device_peak_bytes, program_config, span_seconds, unreported_calls



def stratified_lengths(mix: dict) -> list:
    """The lengths of one block: the block's quantiles of the log-normal,
    rounded up to the buckets."""
    med, sig = mix["prompt_lognormal"]["median"], mix["prompt_lognormal"]["sigma"]
    buckets = sorted(mix["prompt_buckets"])
    nd = statistics.NormalDist()
    out = []
    for i in range(mix["block"]):
        want = med * float(np.exp(sig * nd.inv_cdf((i + 0.5) / mix["block"])))
        out.append(next((b for b in buckets if b >= want), buckets[-1]))
    return out


def request_pool(mix: dict, vocab: int, seed: int) -> list:
    """Prompts in the order the clients send them: the same lengths for
    every seed, shuffled within each block, with tokens drawn from the seed."""
    from bench.weights import seed_words

    rng = np.random.default_rng([*seed_words(seed).tolist(), 1])
    base = np.asarray(stratified_lengths(mix))
    pool = []
    for _ in range(mix["pool_blocks"]):
        for n in rng.permutation(base):
            pool.append(rng.integers(0, vocab, size=int(n), dtype=np.int32))
    return pool


def run(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float, trace: bool,
        limits: dict, tmp: str, t_process: float, log, control: bool = False) -> dict:
    """One run: set-up, the window, the THAPI tally, then the comparison
    with the reference (and, with ``control``, the fp8 control's reading on
    the same rows).  Returns the ``ctx`` that the metric readers take."""
    import jax

    from bench import flops
    from bench.weights import dims, make_weights
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

    # warm-up: one request per length the pool holds runs its prefill, the
    # cache splice and one decode step; the engine's stopping length is set
    # to 1 for it, so no request decodes further
    eng.cfg.max_new_tokens = 1
    for n in sorted({len(p) for p in pool}):
        eng.submit(pool[0][:1].repeat(n))
    eng.run_until_drained()
    eng.completed.clear()
    eng.cfg.max_new_tokens = out_tokens

    sent = {}  # rid → (client, submit time, request)
    waiting = set()  # rids sent and not served yet
    nxt = 0

    def submit(client: int):
        nonlocal nxt
        r = eng.submit(pool[nxt % len(pool)])
        sent[r.rid] = (client, time.perf_counter(), r)
        waiting.add(r.rid)
        nxt += 1

    # ramp: client k starts at step k·out/clients, so completions spread out
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
    step_s = []  # (seconds, requests admitted) of each engine step
    gc.collect()
    gc.freeze()  # the pool and set-up's objects are never scanned in the window
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

    mem_peak = device_peak_bytes()
    tokens = sum(len(r.out_tokens) - at_start.get(rid, 0) for rid, (_, _, r) in sent.items())
    finished = [sent[rid][2] for rid in served if sent[rid][2].done]
    short = sum(1 for r in finished if len(r.out_tokens) != out_tokens)
    made = {"prefill": len(served), "decode_step": steps, "dispatch": len(served) + steps}
    unreported, tallied = unreported_calls(made, tally, handle)
    prompts = [len(sent[rid][2].prompt) for rid in served]
    prefill_flops = sum(flops.prefill_flops(n, z) for n in prompts)
    ctx = {
        "setup_s": win.setup_s,
        "window_s": win.window_s,
        "tokens_out": tokens,
        "ttft_s": ttft,
        "requests": len(served),
        "model_flops": prefill_flops + (tokens - len(served)) * flops.decode_flops_per_token(z),
        "prefill_flops": prefill_flops,
        "prefill_span_s": span_seconds(tally, "prefill"),
        "thapi": {"events": handle.events, "dropped": handle.dropped},
        "profile_ev_s": handle.events / fold_s,
        "memory_peak_bytes": mem_peak,
        "dims": z,
        "ssd_calls": [(n, z["L"]) for n in prof_prefills],
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
        f"{handle.dropped} dropped, tally in {fold_s} s; calls made {made} tallied {tallied}")

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

    from bench import reference
    from bench.weights import dims, reference_weights

    z = dims(cfg)
    M = max(len(o) for _, o in rows)
    S = -(-max(len(p) + len(o) - 1 for p, o in rows) // 256) * 256
    toks = np.zeros((len(rows), S), np.int32)
    pos = np.zeros((len(rows), M), np.int32)
    served = np.zeros((len(rows), M), np.int32)
    for i, (p, o) in enumerate(rows):
        seq = np.concatenate([p, o[:-1]])
        toks[i, : len(seq)] = seq
        pos[i] = len(p) - 1 + np.arange(M)
        served[i] = o
    w = reference_weights(cfg, seed)
    out = reference.logit_gaps(z, w, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(served), control)
    return {k: np.asarray(v) for k, v in out.items()}
