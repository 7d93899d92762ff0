"""Run one benchmark cell once on the accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. Resolves the cell's files by name (``bench/core.py``), prints the
   device JAX found and exits non-zero, printing no result, unless it is
   a TPU with as many chips as the cell asks for.
2. Turns on JAX's persistent compilation cache
   (``repro.launch.compile_cache``: ``$JAX_COMPILATION_CACHE_DIR`` or
   ``<checkout>/.jax_cache``) and keeps every executable in it.
3. Sets up and warms up every shape the window uses (``setup_s``).
4. Measures for ``--seconds``; counts executables produced in the window
   (there should be none) and prints the count.
5. With ``--trace 1``, traces a steady part of the window and reports the
   per-layer metrics from the trace and the program's counters instead of
   the end-to-end ones.
6. Checks the window's outputs against the plain reference, prints each
   number compared beside its limit on standard error, and prints the
   result as one JSON object on the last line of standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
# libtpu would otherwise log to a fixed directory under /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = execute(args.workload, args.seed, args.seconds, bool(args.trace))
    if result is None:
        return 2
    print(json.dumps(result), flush=True)
    return 0


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            root: str = ROOT, require_tpu: bool = True):
    """One run of one cell; the result object, or None without the chips.

    ``require_tpu=False`` is for the tests, which drive whole runs on the
    CPU: it skips the look for a chip and the persistent compilation
    cache, and takes the peaks of a v5e; nothing else changes."""
    from pathlib import Path

    from bench import core

    bench_dir = Path(root) / "bench"
    cell = core.resolve(core.load_benchmark(Path(root)), workload, bench_dir)

    import jax

    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    print(f"[bench] device platform={device['platform']} kind={device['kind']}"
          f" count={device['count']}", flush=True)
    if require_tpu and (dev.platform != "tpu" or len(devs) < cell.chips):
        log(f"needs {cell.chips} TPU chip(s); JAX found {len(devs)} "
            f"{dev.platform!r} device(s). Nothing was run.")
        return None

    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache

        log(f"compile cache: {enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    counter = core.CompileCounter()
    counter.install()
    peaks = core.load_peaks(dev.device_kind if require_tpu else "TPU v5 lite",
                            bench_dir)
    ctx = core.Context(cell=cell, seed=seed, seconds=seconds,
                       tracer=core.Tracer(trace), log=log, peaks=peaks)
    drv = cell.driver.Driver(ctx)
    drv.setup()
    setup_s = time.perf_counter() - T0
    log(f"set-up done in {setup_s!r} s")

    counter.active = True
    e2e = drv.window(seconds)
    counter.active = False
    ctx.tracer.stop()
    print(f"[bench] compilations_in_window={counter.count}", flush=True)

    used = devs[: cell.chips]
    stats = [d.memory_stats() or {} for d in used]
    device["memory_peak_bytes"] = max(s.get("peak_bytes_in_use", 0)
                                      for s in stats)

    checks = drv.check()
    correct = all(c.ok for c in checks)

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    breakdown = None
    if trace:
        from bench import trace_reduce

        path = ctx.tracer.xplane()
        if path is None:
            raise RuntimeError("the traced window recorded no trace")
        t = time.perf_counter()
        ctx.trace = trace_reduce.reduce_xplane(path, drv.trace_op_line)
        log(f"trace {os.path.getsize(path)} bytes reduced in "
            f"{time.perf_counter() - t!r} s")
        ctx.tracer.cleanup()
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        breakdown = trace_reduce.breakdown(ctx.trace)
        for m in cell.per_layer:
            value = cell.readers[m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    else:
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    result = {"correct": correct, "attempted": drv.attempted,
              "failed": drv.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        log(f"check {c.name} = {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    return result


if __name__ == "__main__":
    sys.exit(main())
