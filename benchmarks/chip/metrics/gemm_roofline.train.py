"""The fleet GEMMs' share of their roofline: the least time the chip could
take for the logical GEMMs of the traced window (each record's m, n, q and
element width; padding and verification count as time, not as work) over
the device's busy time inside the benchmark's spans around
``CleaveRuntime.execute_step``."""
from benchmarks.chip.flops import gemm_least_time

KIND = "train"


def read(ctx):
    tr = ctx["trace"]
    if ctx["kind"] != KIND or not tr or not ctx["records"]:
        return None
    busy = tr["busy_in_span_s"].get("bench.execute_step", 0.0)
    if busy <= 0:
        return None
    least = sum(gemm_least_time(r.m, r.n, r.q, r.b, ctx["peaks"])
                for r in ctx["records"])
    return 100.0 * least / busy
