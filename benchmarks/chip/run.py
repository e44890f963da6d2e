"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

``<cell>`` names an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix, loop and per-layer metrics are files found by
name (see ``harness``).  The run needs a TPU with as many chips as the
cell asks for and exits non-zero, printing no result, without one.  With
``--trace 0`` the result's metrics are the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of the window gives its per-layer metrics.
The last line of standard output is the result; the numbers that decide
``correct`` come last on standard error, each beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import harness  # noqa: E402


def main(argv=None, require=harness.require_tpu) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.cell(args.workload)
    dev = require(int(cell["workload"]["chips"]))
    # the program's fixed cache path, inside the checkout unless
    # JAX_COMPILATION_CACHE_DIR names another
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    clock = harness.CompileClock()
    print(f"cell {args.workload} seed {args.seed} seconds {args.seconds} "
          f"trace {args.trace}; device {json.dumps(dev)}; compile cache "
          f"{cache}", flush=True)

    out = cell["loop"].run(cell, args.seed, args.seconds, bool(args.trace),
                           clock, T_START)
    win = out["window"]
    print(f"window {win.seconds!r} s, {win.compiles} compiles inside it "
          f"({win.compile_s!r} s: {sorted(set(win.compiled))}); set-up "
          f"{out['end_to_end']['setup_s']!r} s; {json.dumps(out['notes'])}",
          flush=True)

    device = dict(dev, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": None, "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        from benchmarks.chip import flops
        out["ctx"]["peaks"] = flops.peaks(dev["kind"])
        metrics = harness.per_layer(cell["per_layer"], out["ctx"])
        device.update(busy_s=win.reduced["busy_s"],
                      window_s=win.reduced["window_s"])
    else:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in out["end_to_end"].items()}
    checks = harness.judge(out["readings"], cell["limits"])
    result.update(correct=all(c["ok"] for c in checks.values())
                  and out["failed"] == 0, metrics=metrics, device=device)
    if args.trace:
        result["breakdown"] = win.breakdown
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
