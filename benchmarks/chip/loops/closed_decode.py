"""Loop of kind ``closed_decode``: Cleave's fleet-backed serving session
under a closed loop of clients, each of which sends its next request when
its last one retires.

Set-up admits one request of every prompt length the mix can draw (one
reply token each), so that every prefill shape compiles through the
session's own admission path; then every client's first request is
admitted and takes its first decode step.  The window runs whole decode
steps until the first that ends at or after ``--seconds``.  A token's time
is the harness's clock when the step that made it returns.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.chip import compare, flops, harness, weights
from benchmarks.chip import traffic as gen


def window_stats(prompt_lens, times, t0: float, t1: float) -> dict:
    """What the window made, from each request's prompt length and token
    times: the tokens per second over the whole window, the 95th
    percentile of every inter-token gap that ends inside it (a request's
    first token is not a gap), and the context each token was made at."""
    contexts = [p + k for p, ts in zip(prompt_lens, times)
                for k, tk in enumerate(ts) if tk > t0]
    gaps = [b - a for ts in times for a, b in zip(ts, ts[1:]) if b > t0]
    return {"tokens_per_s": len(contexts) / (t1 - t0),
            "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)),
            "contexts": contexts, "gaps": len(gaps)}


def run(cell: dict, seed: int, seconds: float, trace: bool,
        clock: harness.CompileClock, t_start: float) -> dict:
    from repro.api import CleaveRuntime, Fleet

    config, tr = cell["config"], cell["traffic"]
    cfg = harness.arch(config)
    vocab = cfg.vocab_size
    spans = harness.Spans()

    params = weights.make(config, seed)
    rt = CleaveRuntime(arch=cfg, fleet=Fleet.sample(
        int(tr["fleet"]["devices"]), seed=int(tr["fleet"]["seed"])))
    spans.wrap(rt, "execute_step", "bench.execute_step")
    sess = rt.serve_session(params, slots=int(tr["slots"]),
                            page_size=int(tr["page_size"]),
                            max_len=int(tr["max_len"]), backend="jax")
    spans.wrap(sess.kv, "gather", "bench.kv_gather")
    spans.wrap(sess, "step", "bench.step")

    for p in gen.warmup_prompts(tr, vocab, seed):
        sess.submit(p, 1)
    while sess.step() is not None:
        pass

    stream = gen.requests(tr, vocab, seed)
    served, times, live = [], [], []     # served: every request sent
    unverified = 0

    def send():
        prompt, n = next(stream)
        req = sess.submit(prompt, n)
        served.append(req)
        times.append([])
        live.append(len(served) - 1)

    def step():
        """One decode step; stamps each new token, and each client whose
        request retired sends its next."""
        nonlocal unverified
        rep = sess.step()
        t = time.perf_counter()
        unverified += not rep.verified
        for j in list(live):
            req, ts = served[j], times[j]
            ts += [t] * (len(req.tokens) - len(ts))
            if req.done:
                live.remove(j)
                send()
        return t

    for _ in range(int(tr["clients"])):
        send()
    step()

    unverified_setup = unverified
    win = harness.Window(spans, clock, trace)
    t0 = win.open()
    n_steps = 0
    while True:
        t = step()
        n_steps += 1
        if t - t0 >= seconds:
            break
    win.close()
    bad_window = unverified > unverified_setup
    mem = harness.memory_peak_bytes()

    stats = window_stats([r.prompt_len for r in served], times, t0, win.t1)
    in_window = sum(any(tk > t0 for tk in ts) for ts in times)
    records = [r for rep in sess.step_reports[-n_steps:]
               for r in rep.records]
    done = [(np.asarray(req.prompt), list(req.tokens))
            for req in served if req.tokens]
    del sess, rt, params
    gc.collect()

    ref_mod = harness.load_module("references", config["reference"])
    ref_logits = ref_mod.served_logits(
        config, weights.make(config, seed), [p for p, _ in done],
        [s for _, s in done], int(tr["max_len"]))
    readings = compare.decode_readings([s for _, s in done], ref_logits)
    readings["unverified_steps"] = unverified

    ctx = {"kind": "decode", "config": config, "traffic": tr,
           "window_s": win.seconds, "records": records,
           "host_span_s": dict(spans.seconds), "trace": win.reduced,
           "model_flops": flops.decode_flops(config, stats["contexts"])}
    return {
        "end_to_end": {"decode_tokens_per_s": stats["tokens_per_s"],
                       "itl_p95_ms": stats["itl_p95_ms"],
                       "setup_s": t0 - t_start},
        "ctx": ctx, "readings": readings, "window": win,
        "attempted": in_window, "failed": in_window if bad_window else 0,
        "memory_peak_bytes": mem, "served": done,
        "notes": {"window_steps": n_steps,
                  "window_tokens": len(stats["contexts"]),
                  "gaps": stats["gaps"], "requests_checked": len(done),
                  "tokens_checked": sum(len(s) for _, s in done)},
    }
