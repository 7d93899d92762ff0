"""Simulator throughput: reference vs vectorized vs jax backends (this DES).

Measures *simulated requests per second of wall-clock* for the scalar
reference engine (`repro.sim.engine`), the struct-of-arrays vectorized
engine (`repro.sim.vector_engine`), and the fully compiled jax engine
(`repro.sim.jax_engine`) on identically-seeded Azure traces at the
paper's operating point (rate scaled with trace size so the fleet shape
stays representative). The headline `derived` column reports the speedup —
the repo's acceptance bar is ≥10× at the 100k-request scale (measured:
reference 1896 s vs vectorized 33 s ≈ 57× on a 2-core container, with
matching ttft_p99 between the backends).

The vectorized and jax backends are fed the trace in its native columnar
form (:class:`~repro.traces.generator.TraceColumns`, straight from
``generate_trace_columns``); the reference backend gets the materialized
``Request`` objects. ``--pools 3`` swaps the classic short/long pair for
the 4K/16K/64K three-pool topology, exercising the N-way routing path.
When ``jax`` is among the backends all backends run with spillover off
(the jax tier simulates static N-way routing only), and the one-off XLA
compile is reported as a separate ``jax_compile`` row so the steady-state
``us_per_call`` stays comparable.

``--grid G`` benchmarks the batched sensitivity-sweep API
(:func:`repro.sim.run_fleet_grid`): one vmapped G-lane threshold sweep
against the serial vectorized loop over the same G thresholds. The repo's
acceptance bar is ≥5× steady-state at G=16 (measured: serial 20.7 s vs
grid 3.6 s ≈ 5.7× on a 1-core container).

CLI::

    python -m benchmarks.sim_throughput                   # 10k + 100k
    python -m benchmarks.sim_throughput --requests 1000   # CI smoke
    python -m benchmarks.sim_throughput --requests 1000 --pools 3 \
        --backends vectorized                             # N-pool smoke
    python -m benchmarks.sim_throughput --requests 1000 \
        --backends vectorized,jax --grid 16               # jax tier + sweep
    python -m benchmarks.sim_throughput --requests 1000000 \
        --backends vectorized                             # 1M, vector only

The 1M scale is practical only for the vectorized backend (the reference
engine needs ~1.5 h); pass ``--backends reference,vectorized`` explicitly if
you really want the scalar number.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.beyond_paper_threepool import (
    analytic_profiles,
    pool_configs,
    thresholds_for,
)
from benchmarks.common import emit, write_json
from repro.core.pools import PoolConfig, n_seq_for_cmax
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import TelemetryConfig
from repro.sim import A100_LLAMA3_70B, plan_fleet, run_fleet, run_fleet_grid
from repro.traces import TraceSpec, generate_trace_columns

#: Arrival rate per 10k trace requests — keeps sim duration ≈ 100 s and the
#: planned fleet shape constant across scales.
RATE_PER_10K = 100.0


def build_pools(cols, rate: float, n_pools: int):
    """Pool topology + routing thresholds for the benchmark fleet."""
    if n_pools == 2:
        plan = plan_fleet("azure", cols.to_requests(), A100_LLAMA3_70B, rate)
        return {
            "short": (
                PoolConfig("short", 8192, n_seq_for_cmax(8192), headroom=1.05),
                plan.short.instances,
            ),
            "long": (
                PoolConfig("long", 65_536, 16, headroom=1.02),
                plan.long.instances,
            ),
        }, None
    profiles = analytic_profiles(cols, n_pools, rate, cols.true_total)
    pools = {
        p.pool: (cfg, max(1, p.instances))
        for cfg, p in zip(pool_configs(n_pools), profiles)
    }
    return pools, list(thresholds_for(n_pools))


def bench_scale(
    num_requests: int,
    backends: tuple[str, ...] = ("reference", "vectorized"),
    *,
    seed: int = 42,
    warmup: bool = True,
    n_pools: int = 2,
) -> dict[str, float]:
    """Run one trace size through each backend; returns wall seconds each.

    The jax backend is AOT-compiled first via
    :func:`repro.sim.jax_engine.aot_compile` — the ``jax_compile`` row
    reports the ``.lower()``/``.compile()`` walls alone, with no run
    attached — and a ``jax_carry`` row records the while-loop carry
    footprint from :func:`repro.sim.jax_engine.carry_report`. The timed
    call then hits the executable cache, and its row carries the
    ``jax_iters``/``jax_rounds`` loop counters from
    :func:`repro.sim.jax_engine.last_run_stats`. When jax participates,
    every backend runs with spillover off so the rows stay like-for-like
    (the compiled engine simulates static N-way routing).
    """
    rate = max(50.0, RATE_PER_10K * num_requests / 10_000)
    cols = generate_trace_columns(
        TraceSpec(trace="azure", num_requests=num_requests, rate=rate, seed=seed)
    )
    pools, thresholds = build_pools(cols, rate, n_pools)
    spillover = "jax" not in backends
    # Materialize objects once, outside the timing, for the reference
    # backend; the vectorized and jax backends consume the columns natively.
    reqs = cols.to_requests() if "reference" in backends else None

    if warmup and "vectorized" in backends:
        # JIT-compile the routing/calibration kernels outside the timing.
        # The ramped epoch schedule (64, 128, …, 2048) needs 4032 requests
        # to reach the full 2048-wide padded route-kernel shape; 4096
        # covers every shape the timed run will use.
        run_fleet(
            cols.head(min(len(cols), 4096)),
            pools,
            A100_LLAMA3_70B,
            backend="vectorized",
            thresholds=thresholds,
            spillover=spillover,
        )

    tag = "" if n_pools == 2 else f"/pools={n_pools}"
    walls: dict[str, float] = {}
    for backend in backends:
        trace = reqs if backend == "reference" else cols
        if backend == "jax":
            from repro.sim import FleetSim, jax_engine

            # Compile ahead of time so the jax_compile row is the
            # lower+compile wall alone and the timed run below is a pure
            # executable-cache hit.
            probe = FleetSim(
                pools,
                A100_LLAMA3_70B,
                backend="jax",
                thresholds=thresholds,
                spillover=spillover,
            )
            stats = jax_engine.aot_compile(probe, cols)
            emit(
                f"sim_throughput/jax_compile/n={num_requests}{tag}",
                (stats["lower_s"] + stats["compile_s"]) * 1e6,
                f"aot=1;lower_s={stats['lower_s']:.3f};"
                f"compile_s={stats['compile_s']:.3f}",
            )
            carry = jax_engine.carry_report(probe, cols)
            emit(
                f"sim_throughput/jax_carry/n={num_requests}{tag}",
                0.0,
                f"carry_bytes={carry['carry_bytes']};"
                f"drain_carry_bytes={carry['drain_carry_bytes']};"
                f"sweep_carry_bytes={carry['sweep_carry_bytes']};"
                f"record_bytes={carry['record_bytes']}",
            )
            # Warm the host-side path (budget precompute kernels, array
            # staging) so the timed call measures steady state.
            run_fleet(
                trace,
                pools,
                A100_LLAMA3_70B,
                backend=backend,
                thresholds=thresholds,
                spillover=spillover,
            )
        t0 = time.perf_counter()
        res = run_fleet(
            trace,
            pools,
            A100_LLAMA3_70B,
            backend=backend,
            thresholds=thresholds,
            spillover=spillover,
        )
        wall = time.perf_counter() - t0
        walls[backend] = wall
        extra = ""
        if backend == "jax":
            rs = jax_engine.last_run_stats()
            extra = f";jax_iters={rs['iters']};jax_rounds={rs['rounds']}"
        emit(
            f"sim_throughput/{backend}/n={num_requests}{tag}",
            wall * 1e6,
            f"req_per_s={num_requests / wall:.0f};completed={res.summary.completed};"
            f"rejected={res.summary.rejected};preempt={res.preemptions};"
            f"ttft_p99={res.summary.ttft_p99:.3f}{extra}",
        )
    if "reference" in walls and "vectorized" in walls:
        emit(
            f"sim_throughput/speedup/n={num_requests}{tag}",
            0.0,
            f"x{walls['reference'] / walls['vectorized']:.1f}",
        )
    if "vectorized" in walls and "jax" in walls:
        emit(
            f"sim_throughput/jax_speedup/n={num_requests}{tag}",
            0.0,
            f"x{walls['vectorized'] / walls['jax']:.1f}",
        )
    return walls


def bench_grid_speedup(
    grid_points: int = 16, num_requests: int = 800, *, seed: int = 42
) -> dict[str, float]:
    """Vmapped threshold sweep (`run_fleet_grid`) vs the serial vectorized loop.

    One short/long fleet with the long pool overcommitted vLLM-style
    (``n_seq × blocks_for(c_max) > total_blocks``), swept over
    ``grid_points`` routing thresholds between 512 and 8192 tokens — the
    fig6 sensitivity shape. The serial baseline runs the vectorized
    backend once per threshold (spillover off, matching the grid
    semantics); the grid runs all lanes as one vmapped device
    computation. Compile wall (first call) is emitted separately; the
    ``grid_speedup`` row is serial over steady-state and the acceptance
    bar is ≥5× at G=16 (measured 5.7× on a 1-core container: serial
    20.7 s vs grid 3.6 s at the 800-request default).
    """
    rate = 40.0 * num_requests / 1000
    cols = generate_trace_columns(
        TraceSpec(trace="azure", num_requests=num_requests, rate=rate, seed=seed)
    )
    pools = {
        "short": (PoolConfig("short", 8192, 24, headroom=1.05), 1),
        "long": (PoolConfig("long", 65_536, 20, headroom=1.02), 1),
    }
    thresholds = [[int(b)] for b in np.linspace(512, 8192, grid_points)]

    # Warm the routing/calibration kernels outside the serial timing.
    run_fleet(
        cols,
        pools,
        A100_LLAMA3_70B,
        backend="vectorized",
        thresholds=thresholds[0],
        spillover=False,
    )
    t0 = time.perf_counter()
    serial = [
        run_fleet(
            cols,
            pools,
            A100_LLAMA3_70B,
            backend="vectorized",
            thresholds=th,
            spillover=False,
        )
        for th in thresholds
    ]
    serial_wall = time.perf_counter() - t0

    from repro.sim import jax_engine

    t0 = time.perf_counter()
    run_fleet_grid(cols, pools, A100_LLAMA3_70B, thresholds=thresholds)
    first_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    grid = run_fleet_grid(cols, pools, A100_LLAMA3_70B, thresholds=thresholds)
    steady_wall = time.perf_counter() - t0
    rs = jax_engine.last_run_stats()

    g = grid_points
    emit(
        f"sim_throughput/grid/serial_vectorized/g={g}",
        serial_wall * 1e6,
        f"n={num_requests};per_lane_s={serial_wall / g:.2f};"
        f"completed={sum(r.summary.completed for r in serial)}",
    )
    # The first call above paid lower+compile+run; report the AOT
    # lower/compile walls (recorded inside the executable cache) so the
    # compile row measures compilation alone.
    gstats = [s for s in jax_engine.compile_stats() if s["grid"]][-1]
    compile_wall = gstats["lower_s"] + gstats["compile_s"]
    emit(
        f"sim_throughput/grid/jax_compile/g={g}",
        compile_wall * 1e6,
        f"aot=1;lower_s={gstats['lower_s']:.3f};"
        f"compile_s={gstats['compile_s']:.3f};first_call_s={first_wall:.3f}",
    )
    emit(
        f"sim_throughput/grid/jax_steady/g={g}",
        steady_wall * 1e6,
        f"n={num_requests};per_lane_s={steady_wall / g:.2f};"
        f"completed={int(grid.completed.sum())};"
        f"jax_iters={rs['iters']};jax_rounds={rs['rounds']}",
    )
    emit(
        f"sim_throughput/grid_speedup/g={g}",
        0.0,
        f"x{serial_wall / steady_wall:.1f};"
        f"incl_compile_x{serial_wall / first_wall:.1f}",
    )
    return {
        "serial": serial_wall,
        "compile": compile_wall,
        "first": first_wall,
        "steady": steady_wall,
    }


def bench_telemetry_overhead(
    num_requests: int = 10_000, *, seed: int = 42, window: int = 200
) -> dict[str, float]:
    """Telemetry cost on the vectorized hot path: off vs sampling vs tracing.

    Three identically-seeded runs of the same fleet: telemetry fully off
    (the default — only ``tracer is None`` guards on the hot path), windowed
    sampling only, and sampling + event tracing. The *off* run is the
    configuration CI's throughput gate sees, so its overhead relative to the
    other rows is what the <3% acceptance bar constrains; the ``overhead``
    row reports both enabled modes relative to off. Best-of-3 wall times to
    suppress scheduler noise at CI scale.
    """
    rate = max(50.0, RATE_PER_10K * num_requests / 10_000)
    cols = generate_trace_columns(
        TraceSpec(trace="azure", num_requests=num_requests, rate=rate, seed=seed)
    )
    pools, thresholds = build_pools(cols, rate, 2)
    modes = {
        "off": None,
        "sampling": TelemetryConfig(window=window),
        "tracing": TelemetryConfig(window=window, events=True),
    }
    # JIT warmup (see bench_scale).
    run_fleet(
        cols.head(min(len(cols), 4096)),
        pools,
        A100_LLAMA3_70B,
        backend="vectorized",
        thresholds=thresholds,
    )
    walls: dict[str, float] = {}
    for mode, telemetry in modes.items():
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            run_fleet(
                cols,
                pools,
                A100_LLAMA3_70B,
                backend="vectorized",
                thresholds=thresholds,
                telemetry=telemetry,
            )
            best = min(best, time.perf_counter() - t0)
        walls[mode] = best
        emit(
            f"sim_throughput/telemetry/{mode}/n={num_requests}",
            best * 1e6,
            f"req_per_s={num_requests / best:.0f}",
        )
    emit(
        f"sim_throughput/telemetry/overhead/n={num_requests}",
        0.0,
        f"sampling_pct={100 * (walls['sampling'] / walls['off'] - 1):.1f};"
        f"tracing_pct={100 * (walls['tracing'] / walls['off'] - 1):.1f}",
    )
    return walls


def run() -> None:
    """Aggregate-suite entry (`python -m benchmarks.run`).

    Both host backends at 10k; vectorized-only at 100k (the reference
    backend needs ~30 min there — run it explicitly via the CLI when you
    want the full-scale speedup number); a 10k three-pool vectorized run
    covers the N-way routing path, a telemetry on/off comparison
    quantifies the observability overhead, vectorized-vs-jax pairs at 1k
    and 10k track the compiled single-fleet tier (AOT compile time and
    carry footprint as separate rows, loop counters on the jax rows),
    and the 16-point grid sweep tracks the vmapped-sensitivity speedup
    bar.
    """
    bench_scale(10_000)
    bench_scale(10_000, ("vectorized",), n_pools=3)
    bench_scale(100_000, ("vectorized",))
    bench_telemetry_overhead(10_000)
    bench_scale(1_000, ("vectorized", "jax"))
    bench_scale(10_000, ("vectorized", "jax"))
    bench_grid_speedup(16)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--requests",
        type=int,
        nargs="+",
        default=[10_000, 100_000],
        help="trace sizes to benchmark",
    )
    parser.add_argument(
        "--backends",
        type=str,
        default=None,
        help="comma-separated subset of reference,vectorized,jax "
        "(default: reference,vectorized; vectorized-only at ≥1M)",
    )
    parser.add_argument(
        "--pools",
        type=int,
        default=2,
        choices=(1, 2, 3),
        help="pool topology: 2 = short/long (default), 3 = 4K/16K/64K",
    )
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument(
        "--telemetry-overhead",
        action="store_true",
        help="also benchmark telemetry off/sampling/tracing at each size",
    )
    parser.add_argument(
        "--grid",
        type=int,
        default=0,
        metavar="G",
        help="also benchmark a G-point run_fleet_grid threshold sweep "
        "against the serial vectorized loop (acceptance bar: ≥5× at G=16)",
    )
    parser.add_argument(
        "--json",
        metavar="PATH",
        default=None,
        help="write the emitted rows as a JSON artifact (see benchmarks.common)",
    )
    args = parser.parse_args()
    enable_compile_cache()
    for n in args.requests:
        if args.backends:
            backends = tuple(args.backends.split(","))
        else:
            backends = (
                ("vectorized",) if n >= 1_000_000 else ("reference", "vectorized")
            )
        bench_scale(n, backends, seed=args.seed, n_pools=args.pools)
        if args.telemetry_overhead:
            bench_telemetry_overhead(n, seed=args.seed)
    if args.grid:
        bench_grid_speedup(args.grid, seed=args.seed)
    if args.json:
        # fold the simlint static-pass cost into the same artifact so the
        # CI gate's price shows up next to the engine rows in BENCH_sim.json
        from benchmarks.analysis_throughput import bench_simlint

        bench_simlint()
        write_json(args.json)


if __name__ == "__main__":
    main()
