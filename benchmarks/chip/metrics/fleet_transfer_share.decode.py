"""Share of the decode window spent moving data between the host and the
device inside the fleet GEMMs: the program's ``cleave.fleet.d2h`` (both
operands to the host), ``cleave.fleet.fetch`` (the bucket outputs to the
host) and ``cleave.fleet.h2d`` (the output back to the device) spans,
summed over the window's ``GemmRecord.phases``."""
from benchmarks.chip.program_spans import phase_share

KIND = "decode"


def read(ctx):
    return phase_share(ctx, KIND, ("d2h", "fetch", "h2d"))
