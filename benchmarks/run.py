"""Benchmark harness — one module per paper table + the roofline deliverable.

``PYTHONPATH=src python -m benchmarks.run [table1 table2 ...]``
Each row: ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import sys
import time

from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    from benchmarks import (ablation_loss, perf_compare, roofline_table,
                            table1_precision, table2_beam, table3_clusters,
                            table4_kmeans, table5_ppl, table6_qualitative)
    tables = {
        "table1": table1_precision.run,
        "table2": table2_beam.run,
        "table3": table3_clusters.run,
        "table4": table4_kmeans.run,
        "table5": table5_ppl.run,
        "table6": table6_qualitative.run,
        "ablation": ablation_loss.run,
        "roofline": roofline_table.run,
        "perf": perf_compare.run,
    }
    wanted = sys.argv[1:] or list(tables)
    print("name,us_per_call,derived")
    for name in wanted:
        t0 = time.time()
        tables[name]()
        print(f"# {name} done in {time.time() - t0:.0f}s", file=sys.stderr)


if __name__ == "__main__":
    enable_compile_cache()
    main()
