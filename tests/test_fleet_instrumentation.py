"""Loop counters, phase scopes and host spans of the compiled fleet tier.

``jax_engine`` counts what the device executed inside the event loop
(admission waves, eviction passes and need, live slot rows) and times
each entry call's host phases as ``repro.sim.*`` spans. These tests pin
the counters' invariants on tiny traces, in both modes, and check that
the phase scopes reach the lowered program's op metadata. Record parity
with the host engines is ``test_vector_engine.py``'s.
"""

from __future__ import annotations

import re

import jax
import numpy as np
import pytest

from repro.core.pools import PoolConfig
from repro.core.router import Request
from repro.sim import jax_engine
from repro.sim.fleet import FleetSim
from repro.sim.timing import TimingModel

DYADIC = TimingModel("dyadic", w_base=2**-10, h_per_seq=2**-13, prefill_chunk=512)

SCOPES = ("dispatch", "window_step", "admit", "advance", "record_scatter",
          "evict", "round_update", "fold")

SPANS = ("repro.sim.run", "repro.sim.route_precompute", "repro.sim.compile",
         "repro.sim.stage", "repro.sim.device_run", "repro.sim.results")

GRID_POOLS = {
    "short": (PoolConfig("short", 2048, 8), 2),
    "long": (PoolConfig("long", 8192, 8), 2),
}


def _trace(n, rate, seed, l_in, l_out):
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
    return [
        Request(
            request_id=i,
            byte_len=int(rng.integers(4, 12_000)),
            max_output_tokens=int(rng.integers(*l_out)),
            category=int(rng.integers(0, 4)),
            arrival_time=float(arrivals[i]),
            true_input_tokens=int(rng.integers(*l_in)),
            true_output_tokens=int(rng.integers(*l_out)),
        )
        for i in range(n)
    ]


def _single(trace, cfg, instances, total_blocks=None):
    """One single-lane jax run; (result, last_run_stats)."""
    sim = FleetSim({cfg.name: (cfg, instances)}, DYADIC, backend="jax",
                   coalesce_dt=0.0)
    if total_blocks is not None:
        pool = sim.pools[cfg.name]
        pool.total_blocks = total_blocks
        pool.blocks_free[:] = total_blocks
    res = sim.run(trace)
    return res, jax_engine.last_run_stats()


def _grid(trace, thresholds):
    res = jax_engine.run_fleet_grid(trace, GRID_POOLS, DYADIC,
                                    thresholds=thresholds)
    return res, jax_engine.last_run_stats()


@pytest.fixture(scope="module")
def calm():
    """A single lane whose KV budget never runs short: no preemption."""
    trace = _trace(200, 220.0, 7, (16, 1200), (1, 200))
    return _single(trace, PoolConfig("p", 4096, 16), 3)


@pytest.fixture(scope="module")
def pressed():
    """A single lane on a tiny block budget: constant preemption."""
    trace = _trace(200, 400.0, 3, (16, 900), (1, 400))
    return _single(trace, PoolConfig("p", 1024, 8), 3, total_blocks=90)


@pytest.fixture(scope="module")
def grids():
    """A two-lane threshold grid, and each of its lanes alone."""
    trace = _trace(200, 220.0, 3, (16, 1200), (1, 150))
    both = _grid(trace, [[512], [1536]])
    alone = [_grid(trace, [[t]]) for t in (512, 1536)]
    return both, alone


COUNTERS = ("iters", "rounds", "adm_waves", "evict_runs", "evict_need",
            "live_slot_rounds", "slot_rows", "rounds_total")


def test_counters_exist_in_both_modes(calm, grids):
    (_, grid), _ = grids
    _, fleet = calm
    assert fleet["mode"] == "fleet" and fleet["g"] == 1
    assert grid["mode"] == "grid" and grid["g"] == 2
    for stats in (fleet, grid):
        for key in COUNTERS:
            assert isinstance(stats[key], int), key
        assert "iters_total" not in stats


def test_single_lane_without_pressure_never_evicts(calm):
    res, stats = calm
    assert res.preemptions == 0
    assert stats["evict_runs"] == stats["evict_need"] == 0
    assert stats["adm_waves"] > 0


def test_single_lane_under_pressure_runs_the_gated_pass(pressed):
    res, stats = pressed
    assert res.preemptions > 0
    assert stats["evict_runs"] >= stats["evict_need"] > 0
    assert stats["evict_runs"] < stats["rounds"]


def test_grid_runs_the_pass_every_round(grids):
    (_, both), alone = grids
    for stats in [both] + [s for _, s in alone]:
        assert stats["evict_runs"] == stats["rounds"]
        assert stats["evict_need"] == 0


def test_grid_counts_the_waves_the_device_executed(grids):
    """Lockstep: the batched fixpoint runs until its slowest lane is done,
    so two lanes execute at least the waves of either lane alone."""
    (_, both), alone = grids
    for _, one in alone:
        assert both["adm_waves"] >= one["adm_waves"] > 0
    assert both["rounds"] == max(one["rounds"] for _, one in alone)
    assert both["rounds_total"] == sum(one["rounds"] for _, one in alone)
    assert both["live_slot_rounds"] == sum(
        one["live_slot_rounds"] for _, one in alone)


@pytest.mark.parametrize("which", ["calm", "pressed", "grid"])
def test_live_slot_rounds_within_the_executed_slot_rows(calm, pressed, grids,
                                                        which):
    stats = {"calm": calm[1], "pressed": pressed[1], "grid": grids[0][1]}[which]
    rows = stats["g"] * stats["rounds"] * stats["slot_rows"]
    assert 0 < stats["live_slot_rounds"] <= rows


@pytest.mark.parametrize("which", ["calm", "grid"])
def test_spans_cover_the_call(calm, grids, which):
    stats = calm[1] if which == "calm" else grids[0][1]
    spans = stats["spans"]
    assert set(spans) == set(SPANS)
    assert all(v >= 0.0 for v in spans.values())
    assert spans["repro.sim.device_run"] > 0.0
    children = sum(v for k, v in spans.items() if k != "repro.sim.run")
    assert children <= spans["repro.sim.run"]


def test_spans_belong_to_the_last_call(grids):
    """Each run starts its own record: a later call's spans replace the
    earlier call's, and the call number moves on."""
    (_, both), alone = grids
    assert alone[0][1]["call"] > both["call"]
    assert alone[1][1]["call"] == alone[0][1]["call"] + 1
    # the second lone lane reused the first one's executable
    assert alone[1][1]["spans"]["repro.sim.compile"] < 0.5 * alone[0][1][
        "spans"]["repro.sim.compile"]


def test_compile_stats_keep_their_keys(grids):
    rows = jax_engine.compile_stats()
    assert rows
    for row in rows:
        assert {"n", "return_records", "grid", "g", "lower_s",
                "compile_s"} <= set(row)
        assert row["lower_s"] > 0 and row["compile_s"] > 0


@pytest.mark.parametrize("grid", [False, True], ids=["single", "grid"])
def test_lowered_runner_carries_every_scope(grid):
    """Every phase scope reaches the op metadata (``loc`` names) of the
    lowered program, in both modes (under vmap a scope reads
    ``vmap(<scope>)``)."""
    pools = (jax_engine._PoolSpec("short", 2048, 8, 2048, 2),
             jax_engine._PoolSpec("long", 8192, 8, 2048, 2))
    spec = jax_engine._SimSpec(pools=pools, w=2**-10, h=2**-13,
                               prefill_chunk=512, win_size=16)
    n, g = 64, 2
    with jax.enable_x64():
        fn = jax_engine._runner(spec, n, True, grid)
        text = fn.lower(*jax_engine._abstract_inputs(spec, n, grid, g)
                        ).as_text(debug_info=True)
    for scope in SCOPES:
        assert re.search(rf"[/(]{scope}[)/]", text), scope
