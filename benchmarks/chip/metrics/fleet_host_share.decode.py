"""Share of the decode window spent on the fleet GEMMs' host bookkeeping: the
program's ``cleave.fleet.plan`` (plan lookup and pricing),
``cleave.fleet.tasks`` (task list and bucket geometry) and
``cleave.fleet.scatter`` (blocks into the host output) spans, summed over
the window's ``GemmRecord.phases``."""
from benchmarks.chip.program_spans import phase_share

KIND = "decode"


def read(ctx):
    return phase_share(ctx, KIND, ("plan", "tasks", "scatter"))
