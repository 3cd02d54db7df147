"""``join_ms``: mean ``join_s`` of the program tracer's ``join`` instants
in the window (solo prefill, first token and cache splice of one request),
in ms."""
from bench import readout


def read(art):
    joins = readout.joins_in_window(art)
    if not joins:
        return None
    return 1e3 * sum(s for _, s in joins) / len(joins)
