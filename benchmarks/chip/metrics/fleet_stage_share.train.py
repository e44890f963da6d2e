"""Share of the training window spent staging the fleet GEMMs' operands: the
program's ``cleave.fleet.stage`` span (fingerprint, zero-pad and upload of
both operands through the ``PadCache``), summed over the window's
``GemmRecord.phases``."""
from benchmarks.chip.program_spans import phase_share

KIND = "train"


def read(ctx):
    return phase_share(ctx, KIND, ("stage",))
