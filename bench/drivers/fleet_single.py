"""Driver: one fleet run on the compiled tier, ``FleetSim(backend="jax").run``.

The single-lane path a planner takes for one configuration: a fresh
``FleetSim`` per call (host routing precompute, one device executable,
record back-fill into the host pool shells). Set-up runs one call over a
trace of the same length that ends in a few rounds, which compiles (or
loads) every executable; the window runs calls over the real trace in
whole calls (``fleet_common.run_calls``). The end-to-end metric is
requests simulated per second of the window.

The check compares every call's records with the plain reference.
"""

from __future__ import annotations

import numpy as np

from bench import fleet_common


class Driver:
    trace_op_line = "XLA Modules"

    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.attempted = 0
        self.failed = 0
        self.thresholds = [int(t) for t in ctx.traffic["thresholds"]]
        self.kept = []
        # trace the window's first call, whole (set-up ran one already)
        ctx.tracer.start_s, ctx.tracer.length_s = 0.0, 1e-3

    def _call(self, cols=None) -> dict:
        from repro.sim import FleetSim

        fleet = FleetSim(
            self.pools, self.timing, backend="jax", spillover=False,
            thresholds=self.thresholds,
            epoch=int(self.ctx.config["sim"]["epoch"]),
            calibrator=fleet_common.calibrator(self.ctx.config))
        fleet.run(self.prog_cols if cols is None else cols)
        return self._records(fleet)

    def _records(self, fleet) -> dict:
        n = len(self.cols["request_id"])
        rec = {"first": np.full(n, np.nan), "finish": np.full(n, np.nan),
               "out": np.zeros(n, np.int64), "pre": np.zeros(n, np.int64),
               "trunc": np.zeros(n, bool), "rej": np.zeros(n, bool),
               "pool": np.full(n, -1, np.int64)}
        for name, idx in fleet._pool_index.items():
            a = fleet.pools[name].record_arrays()
            ids = np.asarray(a["request_id"], np.int64)
            rec["first"][ids] = a["first_token"]
            rec["finish"][ids] = a["finish"]
            rec["out"][ids] = a["output_tokens"]
            rec["pre"][ids] = a["preemptions"]
            rec["trunc"][ids] = a["truncated"]
            rec["rej"][ids] = a["rejected"]
            rec["pool"][ids] = idx
        return rec

    def setup(self) -> None:
        self.cols, self.prog_cols, self.pools, self.timing, _ = \
            fleet_common.program_inputs(
            self.ctx.config, self.ctx.traffic, self.ctx.seed)
        self._call(fleet_common.warm_columns(self.cols))

    def window(self, seconds: float) -> dict:
        from repro.sim import jax_engine

        calls, traced, elapsed = fleet_common.run_calls(
            self.ctx, seconds, "bench.fleet_run", self._call, self.kept.append)
        n = len(self.cols["request_id"])
        stats = jax_engine.last_run_stats()
        self.attempted = calls * n
        self.ctx.counters.update(calls=calls, traced_calls=traced, n=n,
                                 rounds=stats["rounds"])
        self.ctx.log(f"{calls} runs of {n} requests; last run stats {stats}")
        return {"sim_lane_req_per_s": self.attempted / elapsed}

    def check(self):
        return fleet_common.lane_checks(self.ctx.config, self.cols,
                                        [(self.thresholds, self.kept)])
