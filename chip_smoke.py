#!/usr/bin/env python3
"""Bring-up smoke run on one TPU chip: PS-centric fleet training and fleet
decode at the published widths of opt-1.3b (d_model 2048, 32 heads of 64,
SwiGLU d_ff 5504, vocab 50272, bfloat16), cut to 4 of its 24 layers, with
random weights from a fixed seed.

    python3 chip_smoke.py                 # one chip: train + serve phases
    python3 chip_smoke.py --four-chips    # 2x2 mesh step vs one chip only

Training phase: ``repro.launch.train`` runs the same seeded job twice in
this process, as the monolithic jitted step (``--backend jax``) and as the
fleet step (``--backend fleet --fleet-exec jax``), where every projection
GEMM and both backward mirrors run through the Pallas band-bucket kernel
with device-side Freivalds.  Every fleet step must verify, every loss must
be finite, and the two loss trajectories must agree within LOSS_RTOL.

Serving phase: a ``CleaveRuntime.serve_session`` on the jax executor
drains 4 seeded 64-token prompts of 16 new tokens each, with the paged
Pallas decode kernel cross-checked against dense attention every step.
Greedy agreement with ``repro.launch.serve``'s monolithic decode is
printed, not required: with random weights, argmax flips on rounding.

Everything runs in one process (a chip belongs to one process at a time).
Without a TPU the script exits non-zero before any phase.  The last line
of standard output is the JSON result; times printed above it come from a
bring-up run and are not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "opt-1.3b"
LAYERS = 4          # depth cut: 4 of 24 layers (~408M params)
SEED = 0

# Both training paths round GEMM operands and outputs to bfloat16 (unit
# roundoff 2^-8 ~ 3.9e-3), but in different places: XLA fusions in the
# jitted step, one rounding per fleet GEMM output in the eager step.  The
# mean loss averages those roundings over batch x seq tokens; 1e-2
# (about 2.5 unit roundoffs) bounds the drift of the mean over 3 steps.
LOSS_RTOL = 1e-2
# Parameters after the mesh step vs the one-chip step: the two reduce in
# different orders, so a gradient near zero may flip sign.  An AdamW step
# moves a weight by at most a few lr (|m_hat / sqrt(v_hat)| <= ~3 in the
# first steps), so the bound is 4 x the summed lr of the run, plus one
# bfloat16 spacing (2^-7 relative) for the rounding of the stored weight.
PARAM_LR_FACTOR = 4.0
PARAM_RTOL = 2.0 ** -7


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache retrievals
    included, which is what a warm cache shortens) and counts cache hits."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == self.EVENT:
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def lap(self):
        return self.seconds, self.cache_hits


def require_tpu(count: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: {json.dumps(dev)}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found "
                         f"{dev['platform']!r}")
    if dev["count"] < count:
        raise SystemExit(f"chip_smoke needs {count} TPU chips; JAX found "
                         f"{dev['count']}")
    return dev


def _train_args(*, layers, batch, seq, steps, backend, extra=()):
    return ["--arch", ARCH, "--layers", str(layers), "--batch", str(batch),
            "--seq", str(seq), "--steps", str(steps), "--seed", str(SEED),
            "--log-every", "1", "--backend", backend, *extra]


def _close(a: float, b: float, rtol: float) -> bool:
    return abs(a - b) <= rtol * max(abs(a), abs(b), 1.0)


def train_phase(*, layers=LAYERS, batch=4, seq=512, steps=3):
    """Monolithic and fleet training of the same seeded job."""
    import math

    from repro.launch import train
    runs = {}
    with tempfile.TemporaryDirectory() as tmp:
        for name, backend, extra in (
                ("monolithic", "jax", ()),
                ("fleet", "fleet", ("--fleet-exec", "jax"))):
            path = os.path.join(tmp, f"train_{name}.json")
            train.main(_train_args(layers=layers, batch=batch, seq=seq,
                                   steps=steps, backend=backend,
                                   extra=extra + ("--metrics-out", path)))
            with open(path) as f:
                runs[name] = json.load(f)
    mono, fleet = runs["monolithic"], runs["fleet"]
    for m, f in zip(mono, fleet):
        print(f"bring-up (not a benchmark) train step {m['step']}: "
              f"loss monolithic {m['loss']:.6f} fleet {f['loss']:.6f} | "
              f"wall monolithic {m['step_time']:.3f}s "
              f"fleet {f['step_time']:.3f}s "
              f"(fleet_exec_time {f['fleet_exec_time']:.3f}s, "
              f"{f['fleet_gemms']} gemms, {f['fleet_tasks']} tasks, "
              f"verified {f['fleet_verified']})", flush=True)
    bad = [r for r in mono + fleet if not math.isfinite(r["loss"])]
    if bad:
        raise SystemExit(f"non-finite loss: {bad}")
    unverified = [f["step"] for f in fleet if not f["fleet_verified"]]
    if unverified:
        raise SystemExit(f"fleet steps {unverified} failed verification")
    apart = [(m["step"], m["loss"], f["loss"]) for m, f in zip(mono, fleet)
             if not _close(m["loss"], f["loss"], LOSS_RTOL)]
    if apart or len(mono) != steps or len(fleet) != steps:
        raise SystemExit(f"fleet and monolithic losses disagree beyond "
                         f"rtol {LOSS_RTOL}: {apart}")
    print(f"train: {steps} steps, fleet and monolithic losses agree within "
          f"rtol {LOSS_RTOL}; every fleet step verified", flush=True)


def serve_phase(*, layers=LAYERS, slots=4, prompt_len=64, max_new=16,
                page_size=16, max_len=128, fleet_devices=16):
    """Fleet decode of seeded prompts, paged-read checks on."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import CleaveRuntime, Fleet
    from repro.configs.base import get_config
    from repro.launch import serve
    from repro.models import model as M

    cfg = dataclasses.replace(get_config(ARCH), n_layers=layers)
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    prompts = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (slots, prompt_len), dtype=np.int32)
    rt = CleaveRuntime(arch=cfg, fleet=Fleet.sample(fleet_devices, seed=0))
    sess = rt.serve_session(params, slots=slots, page_size=page_size,
                            max_len=max_len, backend="jax",
                            check_paged_read=True)
    reqs = [sess.submit(p, max_new=max_new) for p in prompts]
    t0 = time.perf_counter()
    rep = sess.run()
    wall = time.perf_counter() - t0
    steps = sess.step_reports
    print(f"bring-up (not a benchmark) serve: {rep.n_requests} requests, "
          f"{rep.n_tokens} tokens in {rep.n_steps} steps, {wall:.3f}s wall "
          f"(first step {steps[0].wall_time:.3f}s, later steps mean "
          f"{np.mean([s.wall_time for s in steps[1:]]):.3f}s), "
          f"paged_read_checks {sess.paged_read_checks}", flush=True)
    unfinished = [r.rid for r in reqs if len(r.tokens) != max_new]
    if rep.n_requests != slots or unfinished:
        raise SystemExit(f"serve did not drain: {rep.n_requests} finished, "
                         f"short requests {unfinished}")
    unverified = [s.step for s in steps if not s.verified]
    if unverified:
        raise SystemExit(f"serve steps {unverified} failed verification")
    if sess.paged_read_checks <= 0:
        raise SystemExit("the paged decode kernel was never cross-checked")

    mono, _, _ = serve.decode(cfg, params, jnp.asarray(prompts), max_new)
    fleet = np.asarray([r.tokens for r in reqs])
    agree = float(np.mean(fleet == mono))
    print(f"serve: greedy tokens agree with the monolithic decode on "
          f"{agree:.1%} of positions ({int(np.sum(fleet == mono))}/"
          f"{fleet.size}; reported, not required)", flush=True)


def _host_tree(tree):
    import jax
    import numpy as np
    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def four_chip_phase(*, layers=LAYERS, batch=4, seq=512, steps=2):
    """The 2x2-mesh training step against the same steps on one chip."""
    import jax
    import numpy as np

    from repro.launch import train
    p1, h1 = train.run(_train_args(layers=layers, batch=batch, seq=seq,
                                   steps=steps, backend="jax"))
    p1 = _host_tree(p1)
    p4, h4 = train.run(_train_args(layers=layers, batch=batch, seq=seq,
                                   steps=steps, backend="jax",
                                   extra=("--mesh", "2x2")))
    for a, b in zip(h1, h4):
        print(f"bring-up (not a benchmark) step {a['step']}: loss one chip "
              f"{a['loss']:.6f} 2x2 mesh {b['loss']:.6f} | wall one chip "
              f"{a['step_time']:.3f}s mesh {b['step_time']:.3f}s",
              flush=True)
    apart = [(a["step"], a["loss"], b["loss"]) for a, b in zip(h1, h4)
             if not _close(a["loss"], b["loss"], LOSS_RTOL)]
    if apart:
        raise SystemExit(f"mesh and one-chip losses disagree beyond rtol "
                         f"{LOSS_RTOL}: {apart}")

    devices = set(jax.devices())
    per_device = dict.fromkeys(devices, 0)
    total = 0
    for leaf in jax.tree.leaves(p4):
        if leaf.sharding.device_set != devices:
            raise SystemExit(f"a {leaf.shape} parameter lives on "
                             f"{len(leaf.sharding.device_set)} devices, "
                             f"not all {len(devices)}")
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device] += shard.data.nbytes
    share = max(per_device.values()) / total
    print(f"mesh params: every leaf spans all {len(devices)} devices; the "
          f"fullest device holds {share:.1%} of the parameter bytes",
          flush=True)
    if share >= 0.5:
        raise SystemExit("mesh parameters are not sharded: one device "
                         f"holds {share:.1%} of them")

    atol = PARAM_LR_FACTOR * sum(r["lr"] for r in h1)
    worst = 0.0
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(_host_tree(p4))):
        excess = np.abs(a - b) - (atol + PARAM_RTOL * np.abs(a))
        worst = max(worst, float(np.max(excess)) if excess.size else 0.0)
    if worst > 0:
        raise SystemExit(f"mesh and one-chip parameters disagree: worst "
                         f"excess {worst:.3e} over atol {atol:.3e} + rtol "
                         f"{PARAM_RTOL:.3e}")
    print(f"mesh: losses agree within rtol {LOSS_RTOL}; parameters agree "
          f"within atol {atol:.3e} + rtol {PARAM_RTOL:.3e}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh training step and its "
                         "one-chip comparison (needs 4 chips)")
    args = ap.parse_args(argv)

    dev = require_tpu(4 if args.four_chips else 1)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()

    phases = ([("four_chips", four_chip_phase)] if args.four_chips
              else [("train", train_phase), ("serve", serve_phase)])
    for name, phase in phases:
        t0 = time.perf_counter()
        c0, h0 = clock.lap()
        phase()
        c1, h1 = clock.lap()
        print(f"phase {name}: {time.perf_counter() - t0:.1f}s wall, "
              f"{c1 - c0:.1f}s compiling, {h1 - h0} persistent-cache hits",
              flush=True)
    print(f"compile total: {clock.seconds:.1f}s, {clock.cache_hits} "
          f"persistent-cache hits", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
