"""Loop of kind ``train``: Cleave's PS-centric training session on the
fleet, one step after another on fresh seeded rows.

Set-up builds one session and drives its first ``checked_steps`` steps
through the same ``step`` call the window uses.  The window then runs
whole steps until the first that ends at or after ``--seconds``; each
step's clock stops once its loss and updated parameters and moments are on
hand.  The reference follows every step the session took, the checked ones
and the window's: the check compares each step's loss, the first gradient
and the parameters' change over all the steps.
"""
from __future__ import annotations

import gc
import math
import time

from benchmarks.chip import compare, flops, harness, weights
from benchmarks.chip import traffic as gen


def run(cell: dict, seed: int, seconds: float, trace: bool,
        clock: harness.CompileClock, t_start: float) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.api import CleaveRuntime, Fleet
    from repro.optim import adam

    config, tr = cell["config"], cell["traffic"]
    cfg = harness.arch(config)
    vocab = cfg.vocab_size
    spans = harness.Spans()

    def batch(i):
        return {k: jnp.asarray(v)
                for k, v in gen.train_batch(tr, vocab, seed, i).items()}

    params = weights.make(config, seed)
    rt = CleaveRuntime(arch=cfg, fleet=Fleet.sample(
        int(tr["fleet"]["devices"]), seed=int(tr["fleet"]["seed"])))
    spans.wrap(rt, "execute_step", "bench.execute_step")
    opt_cfg = adam.AdamConfig(**config["optimizer"])
    sess = rt.train_session(opt_cfg, backend="jax")
    spans.wrap(sess, "step", "bench.step")
    opt_state = adam.init(params, opt_cfg)

    def step(i):
        nonlocal params, opt_state
        params, opt_state, m = sess.step(params, opt_state, batch(i))
        jax.block_until_ready((params, opt_state))
        return m["fleet"]

    # set-up: the checked steps, through the window's own call
    losses, failed_setup = [], 0
    n_check = int(tr["checked_steps"])
    for i in range(n_check):
        rep = step(i)
        losses.append(rep.loss)
        failed_setup += not rep.verified
        if i == 0:
            b1 = float(config["optimizer"]["b1"])
            grads = {k: v / (1.0 - b1)
                     for k, v in compare.leaf_norms(opt_state.mu).items()}

    win = harness.Window(spans, clock, trace)
    t0 = win.open()
    records, n_steps, failed, i = [], 0, 0, n_check
    while True:
        rep = step(i)
        i += 1
        n_steps += 1
        records += rep.records
        losses.append(rep.loss)
        failed += not (rep.verified and math.isfinite(rep.loss))
        if time.perf_counter() - t0 >= seconds:
            break
    win.close()
    mem = harness.memory_peak_bytes()
    delta = compare.leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        params, weights.make(config, seed)))
    del params, opt_state, sess, rt, rep
    gc.collect()

    ref_mod = harness.load_module("references", config["reference"])
    ref = ref_mod.train(config, weights.make(config, seed),
                        [gen.train_batch(tr, vocab, seed, j)
                         for j in range(i)])
    readings = compare.train_readings(
        {"losses": losses, "grad_norms": grads, "delta_norms": delta}, ref)
    readings["unverified_steps"] = failed_setup + failed

    tokens = n_steps * int(tr["batch"]) * int(tr["seq"])
    ctx = {"kind": "train", "config": config, "traffic": tr,
           "window_s": win.seconds, "records": records,
           "host_span_s": dict(spans.seconds), "trace": win.reduced,
           "model_flops": tokens * flops.train_flops_per_token(
               config, int(tr["seq"]))}
    return {
        "end_to_end": {"train_tokens_per_s": tokens / win.seconds,
                       "setup_s": t0 - t_start},
        "ctx": ctx, "readings": readings, "window": win,
        "attempted": n_steps, "failed": failed,
        "memory_peak_bytes": mem,
        "notes": {"window_steps": n_steps, "losses": losses,
                  "reference_losses": ref["losses"]},
    }
