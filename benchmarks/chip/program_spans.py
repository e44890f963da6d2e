"""What the per-layer metrics of source ``program_span`` read from the
program's own records: the host seconds of each fleet GEMM's phases
(``GemmRecord.phases``, keyed by the short names of the program's
``cleave.fleet.*`` spans) and the GEMM FLOPs its bucket launches ran,
padding included (``GemmRecord.padded_flops``).  Where the records carry
none of what a metric reads, as a program without those spans has not,
the metric reads None."""


def phase_share(ctx: dict, kind: str, names) -> "float | None":
    """The named phases summed over the window's records, as a share of
    the window in %."""
    if ctx["kind"] != kind or not ctx["records"]:
        return None
    phases = [getattr(r, "phases", None) or {} for r in ctx["records"]]
    if not any(n in p for p in phases for n in names):
        return None
    total = sum(p.get(n, 0.0) for p in phases for n in names)
    return 100.0 * total / ctx["window_s"]


def pad_efficiency(ctx: dict, kind: str) -> "float | None":
    """The logical GEMM FLOPs (2 m n q) of the window's records over the
    FLOPs their launches ran, in %."""
    if ctx["kind"] != kind or not ctx["records"]:
        return None
    padded = sum(getattr(r, "padded_flops", 0.0) for r in ctx["records"])
    if padded <= 0:
        return None
    return 100.0 * sum(r.flops for r in ctx["records"]) / padded
