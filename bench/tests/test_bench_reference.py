"""The plain references agree with the program on the CPU, and their
controls, one precision step down, do not pass the limits."""

from __future__ import annotations

import json

import numpy as np
import pytest

from bench import fleet_common
from bench.reference import fleet_des
from bench.tests.tiny import REPO

FLEET = json.loads((REPO / "bench/configs/fleet-azure-1k.json").read_text())
SWEEP = json.loads((REPO / "bench/traffic/sweep16.json").read_text())


#: (pools, trace requests): a paper-shaped fleet cut small, and a stress
#: fleet whose 128-slot long pool runs out of KV blocks, so that
#: preemption, truncation and rejection all occur.
FLEETS = {
    "paper": ([("short", 8192, 128, 2), ("long", 65536, 16, 6)], 400),
    "stress": ([("short", 2048, 32, 1), ("long", 65536, 128, 1)], 600),
}


def _fleet(kind):
    pools, n = FLEETS[kind]
    cfg = json.loads(json.dumps(FLEET))
    cfg["pools"] = [{"name": a, "c_max": c, "n_seq": s, "headroom": 1.0,
                     "instances": i} for a, c, s, i in pools]
    cfg["trace_requests"] = n
    return cfg, fleet_common.program_inputs(cfg, SWEEP, 5)


@pytest.fixture(scope="module")
def small_fleet():
    return _fleet("paper")


@pytest.mark.parametrize("kind,thresholds", [
    ("paper", [512, 4096, 8192]), ("stress", [512, 2048])])
def test_fleet_reference_agrees_with_the_compiled_tier(kind, thresholds):
    from repro.sim import run_fleet_grid

    cfg, (cols, prog, pools, timing, cal) = _fleet(kind)
    res = run_fleet_grid(prog, pools, timing,
                         thresholds=[[t] for t in thresholds],
                         calibrator=cal, epoch=cfg["sim"]["epoch"],
                         return_records=True)
    events = 0
    for k, t in enumerate(thresholds):
        rec = {c: res.records[c][k]
               for c in ("first", "finish", "out", "pre", "trunc", "rej",
                         "pool")}
        checks = fleet_common.lane_checks(cfg, cols, [([t], [rec])])
        assert all(c.ok for c in checks), (t, checks)
        events += int(rec["pre"].sum() + rec["trunc"].sum() + rec["rej"].sum())
    if kind == "stress":
        assert events > 0


def test_fleet_float32_control_fails(small_fleet):
    cfg, (cols, *_rest) = small_fleet
    budget, real = fleet_des.route_budgets(cols, cfg["sim"]["calibrator"],
                                           cfg["sim"]["epoch"])
    pool, _ = fleet_des.pool_choice(budget, real, [8192])
    ctl = fleet_des.simulate(cols, cfg["pools"], cfg["timing"], pool,
                             np.float32)
    ctl["pool"] = pool
    checks = fleet_common.lane_checks(cfg, cols, [([8192], [ctl])])
    assert not all(c.ok for c in checks)


def test_serving_reference_and_fp8_control(tmp_path):
    """At a tiny size on the CPU: the program's served tokens pass the
    logit-gap limit of this size, and the fp8 control fails it on every
    seed (the limit lies between the two readings, as on the chip)."""
    from bench import control
    from bench.tests import tiny

    root = tiny.make(tmp_path, logit_gap=0.2)
    rows = control.run("yi-6b-8l.chat", [1, 2, 3], 3.0, root=str(root),
                       require_tpu=False)
    for r in rows:
        assert r["program"]["logit_gap"] <= 0.2 < r["control"]["logit_gap"]
        assert r["program"]["route_mismatch"] == 0
        assert r["program"]["length_mismatch"] == 0
