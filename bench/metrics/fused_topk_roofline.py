"""``fused_topk_roofline``: see ``bench.readout.fused_topk_roofline``."""
from bench import readout


def read(art):
    return readout.fused_topk_roofline(art)
