"""Find a serving cell's knee by a sweep of offered rates, on the chip.

    python3 bench/knee.py --workload yi-6b-8l.chat --rates 1.5,2,2.5,3 \
        --seconds 51 [--drain 60] [--write]

For each rate, in one process: a fresh set-up of the cell's driver and
one window of ``--seconds`` at that rate. The knee is the highest rate
whose backlog did not grow through the window: no request left
unfinished, and the median time to first token of the last third of the
requests at most twice (or one second more than) that of the first third.
A cell runs at four fifths of it; ``--write`` puts that rate into the
cell's traffic file. Run once when the cell is defined, not by the
benchmark's own runs.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def grows(row: dict) -> bool:
    first, last = row["ttft_med_first_third"], row["ttft_med_last_third"]
    return row["failed"] > 0 or last > max(2 * first, first + 1.0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--drain", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=424242)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    from bench import core
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the knee is found on the chip; no TPU found")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = core.load_benchmark()
    cell = core.resolve(bench, args.workload)
    cell.config["drain_seconds"] = args.drain
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic["rate"] = rate
        ctx = core.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                           tracer=core.Tracer(False), log=lambda m: None)
        drv = cell.driver.Driver(ctx)
        drv.setup()
        metrics = drv.window(args.seconds)
        ttft = np.asarray(drv.ttft)
        k = max(1, len(ttft) // 3)
        row = {"rate": rate, **metrics, "requests": len(ttft),
               "failed": drv.failed,
               "ttft_med_first_third": float(np.median(ttft[:k])),
               "ttft_med_last_third": float(np.median(ttft[-k:]))}
        row["backlog_grows"] = grows(row)
        print(json.dumps(row), flush=True)
        rows.append(row)
        del drv
        gc.collect()
    steady = [r["rate"] for r in rows if not r["backlog_grows"]]
    knee = max(steady) if steady else None
    rate = round(0.8 * knee, 2) if knee else None
    print(json.dumps({"knee": knee, "cell_rate": rate}), flush=True)
    if args.write and rate:
        w = next(w for w in core.with_held_out(bench)["workloads"]
                 if w["name"] == args.workload)
        path = os.path.join(ROOT, "bench", "traffic", f"{w['traffic']}.json")
        traffic = json.loads(open(path).read())
        traffic["rate"] = rate
        with open(path, "w") as f:
            f.write(json.dumps(traffic, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
