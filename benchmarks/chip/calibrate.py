"""Readings that the limits of ``correct`` are set from, for one cell, in
one process on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 11,12,13 --control-seeds 21,22,23 [--seconds 0] [--out f]

For each of ``--seeds`` it runs the cell as ``run.py`` does (a window of
``--seconds``, at least one step) and prints the program's readings: the
lower ends of the limits.  For each of ``--control-seeds`` it prints the
control's readings, the reference computed with float8 GEMMs in the
program's place, and, for a training cell, the faults planted in the
reference: half the batch left out (the mean over the rest), and a step
that leaves the state unchanged (which reads 1 without a run).  A training
control follows ``CONTROL_STEPS`` steps.  A serving
cell's control reads, at each position of a sound run's prompts and
served tokens, the gap of the token that the control puts first.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import compare, harness, weights  # noqa: E402
from benchmarks.chip import traffic as gen  # noqa: E402


# the train cell's 3 checked steps and the 4 that its 51 s window holds on
# a v5e: the run compares every step it took
CONTROL_STEPS = 7


def _seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def train_controls(cell: dict, seed: int, n: int) -> dict:
    config, tr = cell["config"], cell["traffic"]
    ref = harness.load_module("references", config["reference"])
    batches = [gen.train_batch(tr, int(config["vocab_size"]), seed, j)
               for j in range(n)]
    p = weights.make(config, seed)
    want = ref.train(config, p, batches)
    out = {"control": compare.train_readings(
        ref.train(config, p, batches, precision="fp8"), want)}
    out["half_batch"] = compare.train_readings(
        ref.train(config, p, batches, rows=int(tr["batch"]) // 2), want)
    out["state_unchanged"] = compare.train_readings(
        {"losses": want["losses"],
         "grad_norms": dict.fromkeys(want["grad_norms"], 0.0),
         "delta_norms": dict.fromkeys(want["delta_norms"], 0.0)}, want)
    return out


def decode_control(cell: dict, seed: int, served) -> dict:
    import numpy as np
    config, tr = cell["config"], cell["traffic"]
    ref = harness.load_module("references", config["reference"])
    p = weights.make(config, seed)
    prompts, toks = [a for a, _ in served], [b for _, b in served]
    want = ref.served_logits(config, p, prompts, toks, int(tr["max_len"]))
    low = ref.served_logits(config, p, prompts, toks, int(tr["max_len"]),
                            precision="fp8")
    first = [np.argmax(lg, -1) for lg in low]
    return {"control": compare.decode_readings(first, want)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    cell = harness.cell(args.workload)
    dev = harness.require_tpu(int(cell["workload"]["chips"]))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    clock = harness.CompileClock()
    decode = cell["traffic"]["kind"] != "train"
    with open(args.out or os.devnull, "a") as sink:

        def emit(row):
            line = json.dumps(dict(row, workload=args.workload, device=dev))
            print(line, flush=True)
            sink.write(line + "\n")
            sink.flush()

        for seed in _seeds(args.seeds):
            t = time.perf_counter()
            out = cell["loop"].run(cell, seed, args.seconds, False, clock, t)
            emit({"seed": seed, "role": "program",
                  "readings": out["readings"],
                  "end_to_end": out["end_to_end"], "notes": out["notes"]})
            if decode and seed in _seeds(args.control_seeds):
                emit({"seed": seed, "role": "control",
                      **decode_control(cell, seed, out["served"])})
        if not decode:
            for seed in _seeds(args.control_seeds):
                emit({"seed": seed, "role": "control",
                      **train_controls(cell, seed, CONTROL_STEPS)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
