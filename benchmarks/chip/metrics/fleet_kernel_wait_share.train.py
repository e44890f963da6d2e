"""Share of the training window the host spent launching the fleet GEMMs'
bucket kernels and waiting for their outputs: the program's
``cleave.fleet.kernel`` span, summed over the window's
``GemmRecord.phases``."""
from benchmarks.chip.program_spans import phase_share

KIND = "train"


def read(ctx):
    return phase_share(ctx, KIND, ("kernel",))
