"""Benchmark harness: one module per paper table/figure.

``python -m benchmarks.run`` runs everything and prints
``name,us_per_call,derived`` CSV rows (plus a header). ``--json PATH``
additionally writes the whole session as a machine-readable artifact —
per-row ``us_per_call`` + parsed derived metrics + git SHA — so CI can
archive a perf trajectory across commits (see ``benchmarks.common``).

Modules:
  table1_pools        — Table 1 pool configs + μ
  table2_cost         — Table 2 fleet sizes + savings + $/yr
  table3_latency      — Table 3 TTFT/TPOT via fleet DES
  table4_calibration  — Table 4 EMA convergence + mis-route rates
  table5_mi300x       — Table 5 / §4.7 MI300X case study
  fig6_sensitivity    — Fig. 6 threshold sweep
  cost_model_gap      — §4.2 Eq. 7 vs Eq. 8 vs realized
  reliability         — §4.3 preemptions/rejections + fault isolation
  chaos               — §4.3 isolation under injected instance faults
  dispatch_overhead   — §2.2 O(1) sub-microsecond dispatch
  roofline            — §Roofline table from dry-run records
  sim_throughput      — reference/vectorized/jax DES backend speedups
                        + vmapped run_fleet_grid sweep vs serial loop
  telemetry_smoke     — repro.obs telemetry schema + zero-overhead checks
  analysis_throughput — simlint static-pass cost over src/repro

Exits non-zero when any module fails (CI gates on this).
"""

from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks.common import write_json
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--json",
        metavar="PATH",
        default=None,
        help="also write the emitted rows as a JSON artifact "
        "(us_per_call + parsed derived metrics + git SHA)",
    )
    args = ap.parse_args()
    enable_compile_cache()

    from benchmarks import (
        analysis_throughput,
        beyond_paper_adaptive,
        beyond_paper_int8kv,
        beyond_paper_threepool,
        chaos,
        cost_model_gap,
        dispatch_overhead,
        fig6_sensitivity,
        reliability,
        roofline,
        sim_throughput,
        table1_pools,
        table2_cost,
        table3_latency,
        table4_calibration,
        table5_mi300x,
        telemetry_smoke,
    )

    print("name,us_per_call,derived")
    modules = [
        table1_pools,
        table2_cost,
        table3_latency,
        table4_calibration,
        table5_mi300x,
        fig6_sensitivity,
        cost_model_gap,
        reliability,
        chaos,
        dispatch_overhead,
        beyond_paper_int8kv,
        beyond_paper_threepool,
        beyond_paper_adaptive,
        roofline,
        sim_throughput,
        telemetry_smoke,
        analysis_throughput,
    ]
    failed = 0
    errors: list[str] = []
    for mod in modules:
        try:
            mod.run()
        except Exception as e:
            failed += 1
            errors.append(f"{mod.__name__}: {type(e).__name__}: {e}")
            print(f"{mod.__name__},0,ERROR:{type(e).__name__}:{e}", file=sys.stderr)
            traceback.print_exc()
    if args.json:
        write_json(args.json, extra={"failed_modules": errors})
    if failed:
        raise SystemExit(f"{failed} benchmark modules failed")


if __name__ == "__main__":
    main()
