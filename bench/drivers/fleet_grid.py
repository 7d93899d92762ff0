"""Driver: a vmapped threshold sweep, ``repro.sim.run_fleet_grid``.

Set-up generates the trace from the seed and runs one sweep over a trace
of the same length that ends in a few rounds, which compiles (or loads)
the sweep executable and the routing-precompute kernels. The window then
runs the sweep over the real trace in whole calls
(``fleet_common.run_calls``). The end-to-end metric is lane-requests
simulated per second of the window.

The check compares every lane's records from every call of the window
with the plain reference (``bench/reference/fleet_des.py``).
"""

from __future__ import annotations

import numpy as np

from bench import fleet_common

_COLS = ("first", "finish", "out", "pre", "trunc", "rej", "pool")


class Driver:
    trace_op_line = "XLA Modules"

    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.attempted = 0
        self.failed = 0
        self.thresholds = [[int(t)] for t in ctx.traffic["thresholds"]]
        self.lanes = range(len(self.thresholds))
        self.kept = {k: [] for k in self.lanes}
        # trace the window's first call, whole (set-up ran one already)
        ctx.tracer.start_s, ctx.tracer.length_s = 0.0, 1e-3

    def _call(self, cols=None):
        from repro.sim import run_fleet_grid

        sim = self.ctx.config["sim"]
        return run_fleet_grid(
            self.prog_cols if cols is None else cols, self.pools, self.timing,
            thresholds=self.thresholds, calibrator=self.calibrator,
            epoch=int(sim["epoch"]), return_records=True)

    def setup(self) -> None:
        (self.cols, self.prog_cols, self.pools, self.timing,
         self.calibrator) = fleet_common.program_inputs(
            self.ctx.config, self.ctx.traffic, self.ctx.seed)
        self._call(fleet_common.warm_columns(self.cols))

    def window(self, seconds: float) -> dict:
        from repro.sim import jax_engine

        def keep(res):
            for k in self.lanes:
                self.kept[k].append({c: np.asarray(res.records[c][k])
                                     for c in _COLS})

        calls, traced, elapsed = fleet_common.run_calls(
            self.ctx, seconds, "bench.sweep", self._call, keep)
        n, g = len(self.cols["request_id"]), len(self.thresholds)
        stats = jax_engine.last_run_stats()
        self.attempted = calls * g * n
        self.ctx.counters.update(calls=calls, traced_calls=traced, n=n,
                                 rounds=stats["rounds"])
        self.ctx.log(f"{calls} sweeps of {g} lanes x {n} requests; "
                     f"last run stats {stats}")
        return {"sim_lane_req_per_s": self.attempted / elapsed}

    def check(self):
        lanes = [(self.thresholds[k], self.kept[k]) for k in self.lanes]
        return fleet_common.lane_checks(self.ctx.config, self.cols, lanes)
