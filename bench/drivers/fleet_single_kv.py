"""Driver: ``fleet_single`` for a fleet whose pools state their own
per-instance KV block budget.

Each pool of the configuration carries ``kv_blocks``, the 16-token KV
blocks one instance's accelerators hold; the driver builds each
``PoolConfig`` with it (a program without the field fails in set-up with
a ``TypeError``) and checks every call's records against
``bench/reference/fleet_des_kv.py``, the plain reference that honours the
same budget. Everything else, the calls and the window, is
``fleet_single``'s.
"""

from __future__ import annotations

import dataclasses

from bench import fleet_common
from bench.core import Check
from bench.drivers import fleet_single
from bench.reference import fleet_des_kv


class Driver(fleet_single.Driver):
    def setup(self) -> None:
        config = self.ctx.config
        self.cols, self.prog_cols, pools, self.timing, _ = \
            fleet_common.program_inputs(config, self.ctx.traffic,
                                        self.ctx.seed)
        budget = {p["name"]: int(p["kv_blocks"]) for p in config["pools"]}
        self.pools = {
            name: (dataclasses.replace(cfg, kv_blocks=budget[name]), n)
            for name, (cfg, n) in pools.items()
        }
        self._call(fleet_common.warm_columns(self.cols))

    def check(self):
        """Every call's records against the reference, which runs once;
        the worst reading of each number against its limit."""
        ref, mismatch = fleet_des_kv.run_reference(
            self.cols, self.ctx.config, self.thresholds,
            program_pool=self.kept[0]["pool"])
        worst = {"route_mismatch": mismatch, "records_differing": 0,
                 "time_gap_s": 0.0}
        for rec in self.kept:
            for k, v in fleet_des_kv.compare_records(rec, ref).items():
                worst[k] = max(worst[k], v)
        limits = self.ctx.config["checks"]
        return [Check(k, float(v), float(limits[k]))
                for k, v in worst.items()]
