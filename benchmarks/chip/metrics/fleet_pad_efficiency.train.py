"""How much of what the fleet GEMMs' bucket launches ran in the training
window was the GEMMs' own work: the logical FLOPs (2 m n q) of the
window's records over their ``GemmRecord.padded_flops`` (2 bands pm nk qk
per bucket, the padded shapes each launch ran), in %."""
from benchmarks.chip.program_spans import pad_efficiency

KIND = "train"


def read(ctx):
    return pad_efficiency(ctx, KIND)
