"""Benchmark harness entry point: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--full]``

Prints ``name,us_per_call,derived`` CSV (quick mode by default; --full uses
the paper-scale settings).
"""
from __future__ import annotations

import sys


def main() -> None:
    quick = "--full" not in sys.argv
    from benchmarks import (fig1_convergence, fig1_speedup,
                            frontier_stability, nonconvex_frontier,
                            progress_ledger, roofline_report,
                            server_latency, service_throughput,
                            table2_schemes, table3_vs_hogwild)
    table2_schemes.main(quick=quick)
    table3_vs_hogwild.main(quick=quick)
    frontier_stability.main(quick=quick)
    nonconvex_frontier.main(quick=quick)
    service_throughput.main(quick=quick)
    server_latency.main(quick=quick)
    progress_ledger.main(quick=quick)
    fig1_speedup.main(quick=quick)
    fig1_convergence.main(quick=quick)
    roofline_report.main(quick=quick)


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
