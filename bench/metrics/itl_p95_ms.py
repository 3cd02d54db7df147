"""``itl_p95_ms``: 95th percentile of the gap between consecutive tokens of
one request, over the gaps whose later token falls in the window, in ms
(see ``bench.spans.token_gaps_s``)."""
import numpy as np

from bench import spans


def read(art):
    gaps = spans.token_gaps_s(art)
    if not gaps:
        return None
    return 1e3 * float(np.percentile(gaps, 95.0, method="linear"))
