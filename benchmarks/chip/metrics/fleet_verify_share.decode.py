"""Share of the decode window spent checking the fleet GEMMs' blocks on the
host: the program's ``cleave.fleet.verify`` span (the device residuals
against the tolerance, and the host oracle on any flagged block), summed
over the window's ``GemmRecord.phases``."""
from benchmarks.chip.program_spans import phase_share

KIND = "decode"


def read(ctx):
    return phase_share(ctx, KIND, ("verify",))
