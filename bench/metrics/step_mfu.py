"""``step_mfu``: see ``bench.readout.step_mfu``."""
from bench import readout


def read(art):
    return readout.step_mfu(art)
