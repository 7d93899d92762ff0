"""Loop counters, phase scopes and host spans of the compiled fleet tier.

``jax_engine`` counts what the device executed inside the event loop
(admission waves, eviction passes, live slot rows) and times
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


COUNTERS = ("iters", "rounds", "adm_waves", "rej_writes", "rec_trips",
            "evict_runs", "live_slot_rounds", "slot_rows", "rounds_total")


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
    assert stats["evict_runs"] == 0
    assert stats["adm_waves"] > 0


def test_single_lane_under_pressure_runs_the_gated_pass(pressed):
    res, stats = pressed
    assert res.preemptions > 0
    assert 0 < stats["evict_runs"] < stats["rounds"]


def test_grid_gates_the_pass(grids):
    """No lane of the calm grid is ever over budget, so the lane-shared
    gate never runs the pass, whether the lanes run together or alone."""
    (_, both), alone = grids
    for stats in [both] + [s for _, s in alone]:
        assert stats["evict_runs"] == 0


def _records(sim):
    """{request_id: record tuple} of a FleetSim run."""
    out = {}
    for pool in sim.pools.values():
        a = pool.record_arrays()
        for j, rid in enumerate(a["request_id"]):
            out[int(rid)] = (a["first_token"][j], a["finish"][j],
                             int(a["output_tokens"][j]),
                             int(a["preemptions"][j]),
                             bool(a["truncated"][j]), bool(a["rejected"][j]))
    return out


@pytest.fixture(scope="module")
def mixed():
    """A grid whose lanes differ in KV pressure: the same long-request
    trace on one instance (over its block budget: it preempts) and on four
    (never over budget); and each lane as its own FleetSim run. The pool's
    budget is capped at 65,536 blocks, half of what its 64 slots could
    grow to."""
    trace = _trace(120, 100.0, 5, (8000, 16000), (8000, 16000))
    cfg = PoolConfig("p", 65_536, 64)
    grid = jax_engine.run_fleet_grid(trace, {"p": (cfg, 4)}, DYADIC,
                                     instances=[[1], [4]],
                                     return_records=True)
    stats = jax_engine.last_run_stats()
    singles = []
    for inst in (1, 4):
        sim = FleetSim({"p": (cfg, inst)}, DYADIC, backend="jax",
                       coalesce_dt=0.0)
        res = sim.run(trace)
        singles.append((res, _records(sim), jax_engine.last_run_stats()))
    return trace, grid, stats, singles


def test_mixed_pressure_grid_lanes_match_their_single_runs(mixed):
    """On rounds where one lane needs eviction the pass runs for every
    lane; for a lane without need it evicts nothing, so each lane's
    records stay bit-identical to its own single-lane run."""
    trace, grid, _, singles = mixed
    rec = grid.records
    for k, (res, want, _) in enumerate(singles):
        got = {
            r.request_id: (rec["first"][k, j], rec["finish"][k, j],
                           int(rec["out"][k, j]), int(rec["pre"][k, j]),
                           bool(rec["trunc"][k, j]), bool(rec["rej"][k, j]))
            for j, r in enumerate(trace)
        }
        assert got == want, k
        assert int(grid.preemptions[k]) == res.preemptions
    assert grid.preemptions[0] > 0
    assert grid.preemptions[1] == 0


def test_mixed_pressure_grid_runs_the_pass_on_need_rounds_only(mixed):
    _, _, stats, singles = mixed
    assert 0 < stats["evict_runs"] < stats["rounds"]
    pressed_lane, calm_lane = (s for _, _, s in singles)
    assert stats["evict_runs"] >= pressed_lane["evict_runs"] > 0
    assert calm_lane["evict_runs"] == 0


def test_default_budget_never_writes_rejects(calm, pressed, grids, mixed):
    """With the default block budget (and with the pressed fleet's 90
    blocks, over 900-token prompts) no head can need more blocks than its
    pool holds, so no admission wave runs the gated reject write."""
    (_, both), alone = grids
    for stats in [calm[1], pressed[1], both, mixed[2]] + [s for _, s in alone]:
        assert stats["rej_writes"] == 0
        assert stats["adm_waves"] > 0


def _reference_records(sim):
    """{request_id: record tuple} of a reference-engine FleetSim run."""
    return {
        r.request_id: (r.first_token, r.finish, r.output_tokens,
                       r.preemptions, r.truncated, r.rejected)
        for pool in sim.pools.values() for r in pool.records
    }


@pytest.fixture(scope="module")
def tight():
    """A single lane whose block budget (40 blocks, 640 tokens) is below
    some prompts' blocks (16-900 tokens, all under c_max): those heads
    are rejected at admission. The jax run and the reference engine's."""
    trace = _trace(200, 400.0, 3, (16, 900), (1, 400))
    cfg = PoolConfig("p", 1024, 8)
    ref = FleetSim({"p": (cfg, 3)}, DYADIC, backend="reference",
                   coalesce_dt=0.0)
    for inst in ref.pools["p"].instances:
        inst.total_blocks = inst.blocks_free = 40
    ref_res = ref.run(trace)
    sim = FleetSim({"p": (cfg, 3)}, DYADIC, backend="jax", coalesce_dt=0.0)
    sim.pools["p"].total_blocks = 40
    sim.pools["p"].blocks_free[:] = 40
    res = sim.run(trace)
    return (ref_res, _reference_records(ref)), (
        res, _records(sim), jax_engine.last_run_stats())


def test_admission_rejects_match_the_reference(tight):
    (ref_res, want), (res, got, _) = tight
    assert res.rejections == ref_res.rejections > 0
    rejected = {rid for rid, r in want.items() if r[-1]}
    assert rejected == {rid for rid, r in got.items() if r[-1]}
    assert got == want


def test_admission_rejects_run_the_gated_write(tight):
    """Only the waves in which some head rejects run the reject write."""
    _, (_, _, stats) = tight
    assert 0 < stats["rej_writes"] < stats["adm_waves"]


#: Two pools whose short pool holds 40 blocks (640 tokens) an instance:
#: its heads over 640 prompt tokens are rejected at admission.
TIGHT_POOLS = {
    "short": (PoolConfig("short", 1024, 8), 2),
    "long": (PoolConfig("long", 8192, 8), 2),
}


def _tight_trace(n, rate, seed):
    """Prompts of 16-900 tokens whose byte length follows the prompt, so
    a low threshold keeps the long prompts off the short pool."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
    out = []
    for i in range(n):
        inp, outp = int(rng.integers(16, 900)), int(rng.integers(1, 64))
        out.append(Request(request_id=i, byte_len=4 * inp,
                           max_output_tokens=outp, category=0,
                           arrival_time=float(arrivals[i]),
                           true_input_tokens=inp, true_output_tokens=outp))
    return out


@pytest.fixture(scope="module")
def split():
    """A two-lane grid over ``TIGHT_POOLS`` in which one lane rejects at
    admission (threshold 1,024: every request goes short) and the other
    does not (threshold 400: only short prompts do), and each lane alone.
    The short pool's budget comes in through the spec rows."""
    pool_spec = jax_engine._pool_spec

    def tight_rows(name, cfg, max_inst):
        name, c_max, n_seq, total, inst = pool_spec(name, cfg, max_inst)
        return name, c_max, n_seq, 40 if name == "short" else total, inst

    trace = _tight_trace(200, 220.0, 11)
    runs = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax_engine, "_pool_spec", tight_rows)
        for ths in ([[1024], [400]], [[1024]], [[400]]):
            res = jax_engine.run_fleet_grid(trace, TIGHT_POOLS, DYADIC,
                                            thresholds=ths,
                                            return_records=True)
            runs.append((res, jax_engine.last_run_stats()))
    return runs[0], runs[1:]


def test_rejecting_lane_leaves_the_other_lane_unchanged(split):
    """On a wave where one lane rejects, every lane runs the write; a lane
    with no reject writes only its scratch row, so each lane's records
    equal its own lone run."""
    (both, _), alone = split
    assert list(both.rejected) == [int(r.rejected[0]) for r, _ in alone]
    assert both.rejected[0] > 0 and both.rejected[1] == 0
    assert both.routed[1, 0] > 0  # the calm lane still uses the short pool
    for k, (one, _) in enumerate(alone):
        for col, arr in both.records.items():
            np.testing.assert_array_equal(arr[k], one.records[col][0],
                                          err_msg=f"lane {k} {col}")


def test_grid_runs_the_reject_write_for_any_rejecting_lane(split):
    (_, both), alone = split
    rejecting, calm_lane = (s for _, s in alone)
    assert both["rej_writes"] >= rejecting["rej_writes"] > 0
    assert calm_lane["rej_writes"] == 0
    assert both["rej_writes"] < both["adm_waves"]


def _cond_paths(grid):
    """The scope path of every ``cond`` in the runner's jaxpr: the name
    stacks of the equations that enclose it, outermost first, joined by
    ``/`` (a sub-jaxpr's name stack is relative to its equation's)."""
    pools = (jax_engine._PoolSpec("short", 2048, 8, 2048, 2),
             jax_engine._PoolSpec("long", 8192, 8, 2048, 2))
    spec = jax_engine._SimSpec(pools=pools, w=2**-10, h=2**-13,
                               prefill_chunk=512, win_size=16)
    n, g = 64, 2
    with jax.enable_x64():
        fn = jax_engine._runner(spec, n, grid)
        jaxpr = jax.make_jaxpr(fn)(
            *jax_engine._abstract_inputs(spec, n, grid, g)).jaxpr

    def conds(jp, path):
        for eqn in jp.eqns:
            here = path + [str(eqn.source_info.name_stack)]
            if eqn.primitive.name == "cond":
                yield "/".join(p for p in here if p)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from conds(sub, here)

    return list(conds(jaxpr, []))


@pytest.mark.parametrize("grid", [False, True], ids=["single", "grid"])
def test_eviction_pass_stays_a_branch(grid):
    """The eviction pass sits under a ``cond`` in both modes: on a grid
    its predicate is the lane maximum, so vmap keeps the branch instead of
    batching it into a select that runs the pass every round."""
    assert any(re.search(r"(^|/)evict$", name) for name in _cond_paths(grid))


@pytest.mark.parametrize("grid", [False, True], ids=["single", "grid"])
def test_reject_write_stays_a_branch(grid):
    """The admission wave's reject write sits under a ``cond`` inside the
    admission loop in both modes; on a grid the lane-maximum predicate
    keeps it a branch rather than a select that writes every wave."""
    assert any(re.search(r"(^|/)admit$", name) for name in _cond_paths(grid))


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
def test_record_trips_at_most_one_a_round(calm, pressed, grids, which):
    """No round of these traces completes more than one trip's rows, so a
    round runs at most one record-write trip; and each trip a lane runs
    writes at least one of its n requests, each of which completes once."""
    stats = {"calm": calm[1], "pressed": pressed[1], "grid": grids[0][1]}[which]
    assert 0 < stats["rec_trips"] <= stats["rounds"]
    assert stats["rec_trips"] <= stats["g"] * stats["n"]


def test_grid_counts_the_record_trips_the_device_executed(grids):
    """Lockstep: a round's trips run until the lane with the most
    completions is done, so two lanes execute at least the trips of either
    lane alone and at most their sum."""
    (_, both), alone = grids
    trips = [one["rec_trips"] for _, one in alone]
    assert max(trips) <= both["rec_trips"] <= sum(trips)
    assert min(trips) > 0


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
        assert {"n", "grid", "g", "lower_s", "compile_s"} <= set(row)
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
        fn = jax_engine._runner(spec, n, grid)
        text = fn.lower(*jax_engine._abstract_inputs(spec, n, grid, g)
                        ).as_text(debug_info=True)
    for scope in SCOPES:
        assert re.search(rf"[/(]{scope}[)/]", text), scope
