"""``device_idle_share``: see ``bench.readout.device_idle_share``."""
from bench import readout


def read(art):
    return readout.device_idle_share(art)
