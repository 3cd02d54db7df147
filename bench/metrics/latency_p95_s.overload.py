"""``latency_p95_s.overload``: see ``bench.readout.latency_p95_s``."""
from bench import readout


def read(art):
    return readout.latency_p95_s(art)
