"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Heavy sweeps run in reduced
form by default; REPRO_FULL=1 enables paper-scale parameters.

  Fig 5  -> bench_migration_tradeoff      Fig 13/14 -> bench_fault_tolerance
  Fig 8  -> bench_estimator_accuracy      Fig 15    -> cost_efficiency
  Fig 9/10 -> bench_placement             Fig 16    -> bench_init_overlap
  Fig 11 -> bench_beam_width              Table 4   -> bench_calibration
  §Roofline -> roofline_report            §4.2 search -> bench_search_speed
  §5 exec plane -> bench_engine_throughput
  DES cluster sim -> bench_cluster_sim
  paged KV layout -> bench_kv_paging
  length/cost routing -> bench_routing
  hot-path kernels -> bench_kernels
"""

from __future__ import annotations

import sys
import traceback

from benchmarks.common import Rows


def main() -> int:
    """Runs the suites; returns 1 when any of them raised (its row says
    ERROR and the rest still run), else 0."""
    rows = Rows()
    failed = 0
    only = sys.argv[1] if len(sys.argv) > 1 else None
    suites = [
        ("calibration", "benchmarks.bench_calibration"),
        ("estimator_accuracy", "benchmarks.bench_estimator_accuracy"),
        ("migration_tradeoff", "benchmarks.bench_migration_tradeoff"),
        ("beam_width", "benchmarks.bench_beam_width"),
        ("search_speed", "benchmarks.bench_search_speed"),
        ("engine_throughput", "benchmarks.bench_engine_throughput"),
        ("kernels", "benchmarks.bench_kernels"),
        ("kv_paging", "benchmarks.bench_kv_paging"),
        ("prefix_share", "benchmarks.bench_prefix_share"),
        ("routing", "benchmarks.bench_routing"),
        ("placement", "benchmarks.bench_placement"),
        ("fault_tolerance", "benchmarks.bench_fault_tolerance"),
        ("cluster_sim", "benchmarks.bench_cluster_sim"),
        ("init_overlap", "benchmarks.bench_init_overlap"),
        ("roofline", "benchmarks.roofline_report"),
    ]
    ft_out = None
    for name, module in suites:
        if only and only != name:
            continue
        try:
            mod = __import__(module, fromlist=["run"])
            out = mod.run(rows)
            if name == "fault_tolerance":
                ft_out = out
        except Exception as e:
            traceback.print_exc()
            rows.add(f"{name}/ERROR", 0.0, repr(e))
            failed += 1
    if ft_out and (not only or only == "fault_tolerance"):
        try:
            from benchmarks.bench_fault_tolerance import cost_efficiency
            cost_efficiency(ft_out, rows)
        except Exception as e:
            traceback.print_exc()
            rows.add("cost_efficiency/ERROR", 0.0, repr(e))
            failed += 1
    print("name,us_per_call,derived")
    rows.emit()
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
