"""``tick_ms``: see ``bench.readout.tick_ms``."""
from bench import readout


def read(art):
    return readout.tick_ms(art)
