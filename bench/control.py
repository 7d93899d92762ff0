"""Readings of the program and of the control, for setting a cell's limits.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process: one run of the cell's driver (set-up, a
window of ``--seconds``, the check), whose numbers are the program's
readings; then the control's readings on the same work. The control is
the plain reference put in the program's place at the precision below
the one the configuration states:

* fleet cells: the reference simulation with event times in float32
  (the configuration states float64), compared with the float64 one;
* serving cells: the reference forward with fp8 (e4m3) matrix products
  (the configuration states bfloat16), reading at each position of the
  sampled requests the gap of the token fp8 puts first.

A limit goes between the largest program reading and the smallest control
reading. For fleet cells ``--control-only`` reads the control alone (a
host computation, from the seed's trace). The benchmark's own runs never
run the control. One JSON line per seed, then a summary line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def fleet_control(drv) -> dict[str, float]:
    """The float32 reference's records in place of the program's."""
    import numpy as np

    from bench import fleet_common
    from bench.reference import fleet_des

    cfg, cols = drv.ctx.config, drv.cols
    lanes = []
    th_list = ([drv.thresholds[k] for k in drv.lanes]
               if hasattr(drv, "lanes") else [drv.thresholds])
    budget, real = fleet_des.route_budgets(
        cols, cfg["sim"]["calibrator"], int(cfg["sim"]["epoch"]))
    for th in th_list:
        pool, _ = fleet_des.pool_choice(budget, real, th)
        rec = fleet_des.simulate(cols, cfg["pools"], cfg["timing"], pool,
                                 np.float32)
        rec["pool"] = pool
        lanes.append((th, [rec]))
    return {c.name: c.value
            for c in fleet_common.lane_checks(cfg, cols, lanes)}


def serve_control(drv) -> dict[str, float]:
    """fp8 logits at every position of the sampled requests."""
    from bench.reference import transformer as ref_model

    gap = 0.0
    for j in drv.sample:
        g = ref_model.logit_gaps(drv.params, drv.m, drv.sched.prompts[j],
                                 drv.outputs[j], quant="fp8")
        gap = max(gap, float(g.max()))
    return {"logit_gap": gap}


def run(workload: str, seeds: list[int], seconds: float, *, root: str = ROOT,
        require_tpu: bool = True, program: bool = True) -> list[dict]:
    """``program=False`` (fleet cells only) reads the control alone: it
    needs the seed's trace and the reference, not the program's run, and
    so no chip."""
    import gc
    from pathlib import Path

    import jax

    from bench import core

    if require_tpu and program:
        if jax.devices()[0].platform != "tpu":
            raise SystemExit("the control runs on the chip; no TPU found")
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench_dir = Path(root) / "bench"
    cell = core.resolve(core.load_benchmark(Path(root)), workload, bench_dir)
    out = []
    for seed in seeds:
        ctx = core.Context(cell=cell, seed=seed, seconds=seconds,
                           tracer=core.Tracer(False), log=lambda m: None,
                           peaks=core.load_peaks("TPU v5 lite", bench_dir))
        drv = cell.driver.Driver(ctx)
        row = {"seed": seed}
        if program:
            drv.setup()
            drv.window(seconds)
            row["program"] = {c.name: c.value for c in drv.check()}
        else:
            from bench import fleet_common

            drv.cols = fleet_common.program_inputs(
                cell.config, cell.traffic, seed)[0]
        row["control"] = (serve_control(drv) if hasattr(drv, "sample")
                          else fleet_control(drv))
        print(json.dumps(row), flush=True)
        out.append(row)
        del drv
        gc.collect()
    summary = {k: {"control_min": min(r["control"][k] for r in out)}
               for k in out[0]["control"]}
    for k in summary if program else ():
        summary[k]["program_max"] = max(r["program"][k] for r in out)
    print(json.dumps({"summary": summary}), flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-only", action="store_true",
                    help="fleet cells: the control's readings alone")
    args = ap.parse_args()
    run(args.workload, [int(s) for s in args.seeds.split(",")], args.seconds,
        program=not args.control_only)
    return 0


if __name__ == "__main__":
    sys.exit(main())
