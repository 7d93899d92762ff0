"""A small LMSYS-Chat-1M fleet on the compiled tier, run as the benchmark
runs it: ``FleetSim(backend="jax")`` over ``fleet_common.program_inputs``
of the ``fleet-lmsys-1k`` configuration and its ``single-lmsys`` mix, at
3 short x 128 and 1 long x 16 slots and a rate that fills the short
slots past half.

The records must match the plain reference (``bench/reference/
fleet_des.py``). On this fleet the loop counter ``live_peak`` equals the
reference's largest count of requests live at once (admitted and not
finished), alike on a single lane and on each lane of a grid. (A round
sums instances that stand at their own clocks, so on other fleets it
can differ from that count by a few requests.)
"""

from __future__ import annotations

import heapq
import json
import types

import jax
import numpy as np
import pytest

from bench import core, fleet_common
from bench.reference import fleet_des
from repro.sim import FleetSim, jax_engine

N = 1200
RATE = 30.0
THRESHOLDS = (8192, 4096)  # the cell's, and one that sends more traffic long


def _inputs(seed):
    config = json.loads(
        (core.BENCH_DIR / "configs" / "fleet-lmsys-1k.json").read_text())
    traffic = json.loads(
        (core.BENCH_DIR / "traffic" / "single-lmsys.json").read_text())
    config["pools"][0]["instances"], config["pools"][1]["instances"] = 3, 1
    config["trace_requests"] = N
    traffic.update(requests=N, rate=RATE)
    return (config, *fleet_common.program_inputs(config, traffic, seed))


def _program_records(fleet, cols):
    """The program's records, gathered as ``bench/drivers/fleet_single.py``
    gathers them."""
    single = core.load_module(core.BENCH_DIR / "drivers" / "fleet_single.py",
                              "fleet_single")
    return single.Driver._records(types.SimpleNamespace(cols=cols), fleet)


def _reference_with_peak(monkeypatch, cols, config, thresholds, pool):
    """The reference's records, routing mismatches, and its largest count
    of requests live at once: each admitted request is live from the start
    of the engine iteration that admitted it until its finish time (a
    finish and an admission at one instant do not overlap)."""
    now = [0.0]
    admitted = {}

    def heappop(heap):
        item = heapq.heappop(heap)
        now[0] = item[0]
        return item

    class Active(list):
        def append(self, slot):
            admitted[slot[0]] = now[0]
            super().append(slot)

    class Instance(fleet_des._Instance):
        def __init__(self, *args):
            super().__init__(*args)
            self.active = Active()

    with monkeypatch.context() as m:
        m.setattr(fleet_des, "_Instance", Instance)
        m.setattr(fleet_des, "heapq", types.SimpleNamespace(
            heappush=heapq.heappush, heappop=heappop))
        ref, mismatch = fleet_des.run_reference(
            cols, config, list(thresholds), program_pool=pool)
    assert not ref["pre"].any()  # no preemption: one live span a request
    events = sorted([(t, 1) for t in admitted.values()]
                    + [(ref["finish"][rid], -1) for rid in admitted])
    live = np.cumsum([d for _, d in events])
    return ref, mismatch, int(live.max())


@pytest.fixture(scope="module")
def single_runs():
    """The inputs, and each threshold's single-lane (records, stats)."""
    config, cols, prog, pools, timing, _ = _inputs(3)
    runs = {}
    for th in THRESHOLDS:
        fleet = FleetSim(pools, timing, backend="jax", spillover=False,
                         thresholds=[th], epoch=int(config["sim"]["epoch"]),
                         calibrator=fleet_common.calibrator(config))
        fleet.run(prog)
        runs[th] = (_program_records(fleet, cols),
                    jax_engine.last_run_stats())
    return config, cols, prog, pools, timing, runs


@pytest.mark.parametrize("threshold", THRESHOLDS)
def test_single_lane_matches_the_reference(monkeypatch, single_runs,
                                           threshold):
    config, cols, _, _, _, runs = single_runs
    rec, stats = runs[threshold]
    ref, mismatch, peak = _reference_with_peak(
        monkeypatch, cols, config, [threshold], rec["pool"])
    got = fleet_des.compare_records(rec, ref)
    assert mismatch == 0
    assert got["records_differing"] == 0
    assert got["time_gap_s"] <= 1e-9
    assert stats["live_peak"] == peak
    assert stats["real_slot_rows"] == 3 * 128 + 16
    if threshold == 8192:  # the cell's threshold fills the short slots
        assert peak > 0.5 * 3 * 128


def test_grid_lanes_count_the_single_lane_peaks(single_runs):
    config, _, prog, pools, timing, runs = single_runs
    res = jax_engine.run_fleet_grid(
        prog, pools, timing, thresholds=[[t] for t in THRESHOLDS],
        calibrator=fleet_common.calibrator(config),
        epoch=int(config["sim"]["epoch"]))
    stats = jax_engine.last_run_stats()
    want = [runs[t][1]["live_peak"] for t in THRESHOLDS]
    assert res.live_peak.tolist() == want
    assert stats["live_peak"] == max(want)
    assert stats["real_slot_rows"] == 3 * 128 + 16


def test_single_lane_program_sorts_nothing_at_the_cells_length():
    """Float64 sorts of tens of thousands of rows are slow to compile for
    the TPU (about a minute on a v5e at 60,000 requests), so the
    single-lane program, which the cell runs at tens of
    thousands of requests, must lower without one, and so must the grid
    program (its latency percentiles are computed on the host)."""
    _, _, prog, pools, timing, _ = _inputs(3)
    fleet = FleetSim(pools, timing, backend="jax", spillover=False,
                     thresholds=[8192])
    n = int(json.loads((core.BENCH_DIR / "traffic" / "single-lmsys.json")
                       .read_text())["requests"])
    spec = jax_engine._fleet_spec(fleet, prog)[0]
    with jax.enable_x64():
        for grid, g in ((False, 0), (True, 2)):
            text = jax_engine._runner(spec, n, grid).lower(
                *jax_engine._abstract_inputs(spec, n, grid, g)).as_text()
            assert text.count("stablehlo.sort") == 0, grid
