"""Scalar-vs-vectorized simulator equivalence (tentpole acceptance suite).

The vectorized struct-of-arrays backend must reproduce the scalar reference
engine's behaviour. For routerless (single-pool) fleets with ``coalesce_dt=0``
the two are *bit-identical* — completion/preemption/rejection totals, every
per-request record, and all latency percentiles — provided the timing
constants are dyadic (powers of two) so float accumulation is exact in both
engines. Two-pool routed fleets relax routing to per-epoch batches, so those
compare within tolerance.
"""

import dataclasses

import numpy as np
import pytest

from repro.core.pools import PoolConfig, n_seq_for_cmax
from repro.core.router import Request
from repro.sim import A100_LLAMA3_70B, plan_fleet, profile_pool
from repro.sim.fleet import FleetSim, run_fleet
from repro.sim.timing import TimingModel
from repro.traces import TraceSpec, generate_trace, generate_trace_columns

#: Dyadic constants: W, H, and every accumulated event time are exact
#: binary floats, so `now + k*t_iter` (vector) == repeated addition (scalar).
DYADIC = TimingModel("dyadic", w_base=2**-10, h_per_seq=2**-13, prefill_chunk=512)

SUMMARY_FIELDS = (
    "num_requests",
    "completed",
    "rejected",
    "truncated",
    "preemptions",
    "ttft_p50",
    "ttft_p99",
    "tpot_p50",
    "tpot_p99",
    "makespan",
)


def poisson_trace(n, rate, seed, *, l_in=(16, 3000), l_out=(1, 400)):
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


def run_single_pool(trace, config, instances, backend, *, total_blocks=None):
    """One single-pool run; ``total_blocks`` sets each instance's KV block
    budget through the pool's ``kv_blocks``."""
    if total_blocks is not None:
        config = dataclasses.replace(config, kv_blocks=total_blocks)
        assert config.total_blocks == total_blocks
    sim = FleetSim(
        {config.name: (config, instances)},
        DYADIC,
        backend=backend,
        coalesce_dt=0.0,  # exact event ordering
    )
    return sim, sim.run(trace)


def record_tuples(result, sim):
    if result.records is not None:
        recs = result.records
    else:
        recs = [r for p in sim.pools.values() for r in p.records]
    return sorted(
        (
            r.request_id,
            r.arrival,
            r.first_token,
            r.finish,
            r.output_tokens,
            r.preemptions,
            r.truncated,
            r.rejected,
        )
        for r in recs
    )


class TestExactEquivalence:
    def test_seeded_trace_identical(self):
        """Same seeded trace → identical totals, percentiles, and records."""
        trace = poisson_trace(1500, rate=250.0, seed=11)
        cfg = PoolConfig("p", 4096, 16)
        ref_sim, ref = run_single_pool(trace, cfg, 4, "reference")
        vec_sim, vec = run_single_pool(trace, cfg, 4, "vectorized")
        for f in SUMMARY_FIELDS:
            assert getattr(ref.summary, f) == getattr(vec.summary, f), f
        assert ref.preemptions == vec.preemptions
        assert ref.rejections == vec.rejections
        assert record_tuples(ref, ref_sim) == record_tuples(vec, vec_sim)

    def test_adversarial_kv_pressure_trace(self):
        """Tiny block budget: constant preemption + mid-generation truncation
        must match the reference engine decision-for-decision."""
        trace = poisson_trace(
            600, rate=400.0, seed=3, l_in=(16, 900), l_out=(50, 800)
        )
        cfg = PoolConfig("p", 1024, 8)
        ref_sim, ref = run_single_pool(
            trace, cfg, 3, "reference", total_blocks=90
        )
        vec_sim, vec = run_single_pool(
            trace, cfg, 3, "vectorized", total_blocks=90
        )
        # the trace actually exercises the adversarial paths
        assert ref.preemptions > 100
        assert ref.summary.truncated > 50
        for f in SUMMARY_FIELDS:
            assert getattr(ref.summary, f) == getattr(vec.summary, f), f
        assert ref.preemptions == vec.preemptions
        assert ref.rejections == vec.rejections
        # incremental truncation counters (the controller's error signal)
        # match each other and the canonical per-request records
        assert ref.truncations == vec.truncations > 0
        truncated_records = sum(
            1 for r in (ref.records or []) if r.truncated
        )
        assert ref.truncations == truncated_records
        assert record_tuples(ref, ref_sim) == record_tuples(vec, vec_sim)

    def test_rejections_identical(self):
        """Oversized prompts reject identically in both backends."""
        trace = poisson_trace(300, rate=100.0, seed=5, l_in=(16, 3000))
        cfg = PoolConfig("p", 1024, 8)  # prompts ≥ 1024 → submit-time reject
        ref_sim, ref = run_single_pool(trace, cfg, 2, "reference")
        vec_sim, vec = run_single_pool(trace, cfg, 2, "vectorized")
        assert ref.rejections == vec.rejections > 0
        assert record_tuples(ref, ref_sim) == record_tuples(vec, vec_sim)


def three_pool_topology(trace, rate):
    """4K/16K/64K pools sized analytically for this trace (oracle split)."""
    cfgs = (
        PoolConfig("p4k", 4096, n_seq_for_cmax(4096), headroom=1.05),
        PoolConfig("p16k", 16_384, n_seq_for_cmax(16_384), headroom=1.05),
        PoolConfig("p64k", 65_536, 16, headroom=1.02),
    )
    thresholds = [4096, 16_384]
    group = np.searchsorted(thresholds, [r.true_total for r in trace])
    pools = {}
    for k, cfg in enumerate(cfgs):
        members = [r for r, g in zip(trace, group) if g == k]
        prof = profile_pool(cfg.name, trace, members, cfg, A100_LLAMA3_70B, rate)
        pools[cfg.name] = (cfg, max(1, prof.instances))
    return pools, thresholds


class TestRoutedTolerance:
    """Routed fleets batch routing per epoch (calibration lags ≤ one
    epoch), so aggregate metrics agree within tolerance, not bit-for-bit —
    checked for both the classic short/long pair and the 4K/16K/64K
    three-pool topology."""

    @pytest.fixture(scope="class", params=["two_pool", "three_pool"])
    def results(self, request):
        n, rate = 4000, 400.0
        trace = generate_trace(
            TraceSpec(trace="azure", num_requests=n, rate=rate, seed=42)
        )
        if request.param == "two_pool":
            plan = plan_fleet("azure", trace, A100_LLAMA3_70B, rate)
            pools = {
                "short": (
                    PoolConfig("short", 8192, n_seq_for_cmax(8192), headroom=1.05),
                    plan.short.instances,
                ),
                "long": (
                    PoolConfig("long", 65_536, 16, headroom=1.02),
                    plan.long.instances,
                ),
            }
            thresholds = None
        else:
            pools, thresholds = three_pool_topology(trace, rate)
        ref = run_fleet(
            trace, pools, A100_LLAMA3_70B, backend="reference", thresholds=thresholds
        )
        vec = run_fleet(
            trace, pools, A100_LLAMA3_70B, backend="vectorized", thresholds=thresholds
        )
        return ref, vec

    def test_completion_totals_close(self, results):
        ref, vec = results
        assert ref.summary.num_requests == vec.summary.num_requests
        assert vec.summary.completed == pytest.approx(
            ref.summary.completed, rel=0.01
        )

    def test_latency_percentiles_close(self, results):
        ref, vec = results
        assert vec.summary.ttft_p99 == pytest.approx(
            ref.summary.ttft_p99, rel=0.15
        )
        assert vec.summary.tpot_p99 == pytest.approx(
            ref.summary.tpot_p99, rel=0.15
        )

    def test_routing_fractions_close(self, results):
        ref, vec = results
        for name, frac in ref.router_stats["fractions"].items():
            assert vec.router_stats["fractions"][name] == pytest.approx(
                frac, abs=0.02
            ), name

    def test_calibration_converges_both(self, results):
        for res in results:
            assert all(c > 0 for c in res.router_stats["calibration"]["count"])


class TestColumnarInput:
    """TraceColumns is the vectorized backend's native input; feeding the
    columns directly must be indistinguishable from feeding the
    materialized Request objects — on both backends."""

    @pytest.fixture(scope="class")
    def setup(self):
        cols = generate_trace_columns(
            TraceSpec(trace="azure", num_requests=1200, rate=120.0, seed=21)
        )
        plan = plan_fleet("azure", cols.to_requests(), A100_LLAMA3_70B, 120.0)
        pools = {
            "short": (
                PoolConfig("short", 8192, n_seq_for_cmax(8192), headroom=1.05),
                plan.short.instances,
            ),
            "long": (
                PoolConfig("long", 65_536, 16, headroom=1.02),
                plan.long.instances,
            ),
        }
        return cols, pools

    @pytest.mark.parametrize("backend", ["vectorized", "reference"])
    def test_columns_equal_objects(self, setup, backend):
        cols, pools = setup
        res_c = run_fleet(cols, pools, A100_LLAMA3_70B, backend=backend)
        res_o = run_fleet(
            cols.to_requests(), pools, A100_LLAMA3_70B, backend=backend
        )
        for f in SUMMARY_FIELDS:
            assert getattr(res_c.summary, f) == getattr(res_o.summary, f), f
        assert res_c.router_stats["routed"] == res_o.router_stats["routed"]


class TestControllerInTheLoop:
    """Closed-loop adaptive control must behave equivalently through both
    backends: same windows (request counts), same error contract
    (preemptions + rejections + truncations), boundary moves applied to
    the live PoolSet. The feedback loop amplifies the backends' epoch
    staleness, so aggregates compare within loose tolerance while the
    functional claims (controller fires, boundary tightens, thresholds
    stay valid) are exact."""

    @pytest.fixture(scope="class")
    def incident(self):
        """Undersized short pool (capacity incident) + controller."""
        from repro.core.adaptive import AdaptiveController

        n, rate = 2500, 250.0
        cols = generate_trace_columns(
            TraceSpec(trace="azure", num_requests=n, rate=rate, seed=42)
        )
        plan = plan_fleet("azure", cols.to_requests(), A100_LLAMA3_70B, rate)
        pools = {
            "short": (
                PoolConfig(
                    "short", 8192, n_seq_for_cmax(8192),
                    headroom=1.05, queue_limit=64,
                ),
                max(1, int(plan.short.instances * 0.6)),
            ),
            "long": (
                PoolConfig("long", 65_536, 16, headroom=1.02, queue_limit=64),
                plan.long.instances,
            ),
        }
        out = {}
        for backend in ("reference", "vectorized"):
            ctrl = AdaptiveController(b_min=512)
            sim = FleetSim(
                dict(pools), A100_LLAMA3_70B, b_short=8192, backend=backend,
                controller=ctrl, control_window=200,
            )
            trace = cols if backend == "vectorized" else cols.to_requests()
            out[backend] = (sim.run(trace), ctrl)
        return out

    def test_controller_fires_on_both_backends(self, incident):
        for backend, (_, ctrl) in incident.items():
            assert ctrl.history, backend
            assert ctrl.thresholds[0] < 8192, backend

    def test_thresholds_stay_valid_on_both_backends(self, incident):
        for backend, (_, ctrl) in incident.items():
            assert 512 <= ctrl.thresholds[0] <= 8192, backend

    def test_aggregates_close_across_backends(self, incident):
        ref, _ = incident["reference"]
        vec, _ = incident["vectorized"]
        assert ref.summary.num_requests == vec.summary.num_requests
        assert vec.summary.completed == pytest.approx(
            ref.summary.completed, rel=0.02
        )
        # the control loop compounds routing-epoch staleness: compare the
        # operating point loosely, direction is pinned by the tests above
        assert vec.summary.ttft_p99 == pytest.approx(
            ref.summary.ttft_p99, rel=0.5
        )

    def test_router_stats_report_moved_thresholds(self, incident):
        for backend, (res, ctrl) in incident.items():
            assert res.router_stats["thresholds"] == ctrl.thresholds, backend

    def test_controller_requires_multi_pool(self):
        from repro.core.adaptive import AdaptiveController

        with pytest.raises(ValueError):
            FleetSim(
                {"p": (PoolConfig("p", 4096, 16), 1)},
                A100_LLAMA3_70B,
                controller=AdaptiveController(),
            )


class TestCanonicalRecords:
    def test_no_double_counting(self):
        """Every submitted request appears exactly once in the canonical
        record list — completions and rejections never double-count."""
        trace = poisson_trace(500, rate=300.0, seed=9, l_in=(16, 2000))
        cfg = PoolConfig("p", 1024, 8)
        for backend in ("reference", "vectorized"):
            sim, res = run_single_pool(trace, cfg, 2, backend, total_blocks=120)
            recs = record_tuples(res, sim)
            ids = [r[0] for r in recs]
            assert len(ids) == len(set(ids)) == len(trace)
            assert res.summary.completed + res.summary.rejected == (
                res.summary.num_requests
            )

    def test_summary_built_from_canonical_records(self):
        trace = poisson_trace(400, rate=200.0, seed=13)
        cfg = PoolConfig("p", 4096, 8)
        sim, res = run_single_pool(trace, cfg, 2, "reference")
        assert res.records is not None
        from repro.sim.metrics import summarize

        rebuilt = summarize("fleet", res.records, total_spills=0)
        assert rebuilt == res.summary


class TestIncrementalPoolState:
    def test_counters_match_recompute_mid_run(self):
        """PoolState.queue_depth/active stay consistent with a full O(N)
        recompute at every step of a preemption-heavy run (O(1) dispatch)."""
        trace = poisson_trace(200, rate=500.0, seed=7, l_in=(16, 900), l_out=(50, 400))
        cfg = PoolConfig("p", 1024, 4)
        sim = FleetSim({"p": (cfg, 2)}, DYADIC, coalesce_dt=0.0)
        pool = sim.pools["p"]
        for inst in pool.instances:
            inst.total_blocks = 80
            inst.blocks_free = 80

        t = 0.0
        ti = iter(sorted(trace, key=lambda r: r.arrival_time))
        nxt = next(ti, None)
        for _ in range(5000):
            while nxt is not None and nxt.arrival_time <= t:
                pool.least_loaded().submit(nxt, nxt.arrival_time)
                nxt = next(ti, None)
            for inst in pool.instances:
                inst.step(t)
            assert pool.state.queue_depth == sum(
                len(i.queue) for i in pool.instances
            )
            assert pool.state.active == sum(
                len(i.active) for i in pool.instances
            )
            if nxt is None and all(i.idle for i in pool.instances):
                break
            t += DYADIC.iter_time(1)
        assert pool.preemptions > 0  # the run exercised preemption paths


def _sorted_events(telemetry):
    """Time-sorted event multiset — the cross-backend comparison key.

    Within one coalesced round the two backends walk instances in different
    orders (heap order vs row order), so raw emission order differs while
    the event *set* is identical; sorting by (t, kind, request_id, pool,
    value) makes the comparison order-insensitive without losing anything.
    """
    tr = telemetry.events
    idx = tr._order()
    return sorted(
        zip(
            tr.t[idx].tolist(),
            tr.kind[idx].tolist(),
            tr.request_id[idx].tolist(),
            tr.pool[idx].tolist(),
            tr.value[idx].tolist(),
        )
    )


class TestTelemetryEquivalence:
    """The observability layer inherits the backend-equivalence contract:
    exact-class runs (single pool, dyadic timing, ``coalesce_dt=0``) must
    produce *identical* telemetry columns and event multisets from both
    engines; routed fleets compare structurally (same windows, deltas that
    reconcile with the run counters) since routing itself is only
    tolerance-equivalent. Installing telemetry must never perturb the
    simulation."""

    WINDOW = 100

    def _run_single(self, trace, backend, telemetry):
        from repro.obs import TelemetryConfig

        cfg = PoolConfig("p", 4096, 16)
        sim = FleetSim(
            {"p": (cfg, 4)},
            DYADIC,
            backend=backend,
            coalesce_dt=0.0,
            telemetry=telemetry,
            control_window=self.WINDOW,
        )
        return sim.run(trace)

    @pytest.fixture(scope="class")
    def exact(self):
        from repro.obs import TelemetryConfig

        trace = poisson_trace(1500, rate=250.0, seed=11)
        tel = TelemetryConfig(window=self.WINDOW, events=True)
        ref = self._run_single(trace, "reference", tel)
        vec = self._run_single(trace, "vectorized", tel)
        return ref, vec

    def test_exact_class_columns_identical(self, exact):
        ref, vec = exact
        assert ref.telemetry.num_samples == vec.telemetry.num_samples > 0
        assert set(ref.telemetry.columns) == set(vec.telemetry.columns)
        for name in ref.telemetry.columns:
            assert np.array_equal(
                ref.telemetry.column(name),
                vec.telemetry.column(name),
                equal_nan=True,
            ), name

    def test_exact_class_event_multisets_identical(self, exact):
        ref, vec = exact
        a = _sorted_events(ref.telemetry)
        b = _sorted_events(vec.telemetry)
        assert len(a) == len(b) > 0
        assert a == b

    def test_telemetry_off_is_bit_identical(self):
        trace = poisson_trace(800, rate=250.0, seed=17)
        from repro.obs import TelemetryConfig

        for backend in ("reference", "vectorized"):
            plain = self._run_single(trace, backend, None)
            tele = self._run_single(
                trace, backend, TelemetryConfig(window=self.WINDOW, events=True)
            )
            for f in SUMMARY_FIELDS:
                assert getattr(plain.summary, f) == getattr(tele.summary, f), (
                    backend,
                    f,
                )
            assert plain.telemetry is None
            assert tele.telemetry is not None

    @pytest.fixture(scope="class", params=["two_pool", "three_pool"])
    def routed(self, request):
        from repro.obs import TelemetryConfig

        n, rate = 3000, 300.0
        trace = generate_trace(
            TraceSpec(trace="azure", num_requests=n, rate=rate, seed=42)
        )
        if request.param == "two_pool":
            plan = plan_fleet("azure", trace, A100_LLAMA3_70B, rate)
            pools = {
                "short": (
                    PoolConfig("short", 8192, n_seq_for_cmax(8192), headroom=1.05),
                    plan.short.instances,
                ),
                "long": (
                    PoolConfig("long", 65_536, 16, headroom=1.02),
                    plan.long.instances,
                ),
            }
            thresholds = None
        else:
            pools, thresholds = three_pool_topology(trace, rate)
        tel = TelemetryConfig(window=self.WINDOW, events=True)
        out = {}
        for backend in ("reference", "vectorized"):
            out[backend] = run_fleet(
                trace,
                pools,
                A100_LLAMA3_70B,
                backend=backend,
                thresholds=thresholds,
                telemetry=tel,
            )
        return out

    def test_routed_windows_align(self, routed):
        """Windows are counted in dispatched requests on both backends; the
        vectorized engine may overshoot a boundary by at most one dispatch
        chunk (documented in ``repro.obs``), so sample counts agree within
        the merge slack while the request axis itself is identical: both
        series are non-decreasing and end at the full dispatched count."""
        ref, vec = routed["reference"], routed["vectorized"]
        assert ref.telemetry.pool_names == vec.telemetry.pool_names
        for tel in (ref.telemetry, vec.telemetry):
            assert tel.num_samples > 0
            t_req = tel.column("t_req")
            assert np.all(np.diff(t_req) >= 0)
        assert (
            ref.telemetry.column("t_req")[-1]
            == vec.telemetry.column("t_req")[-1]
        )
        assert abs(ref.telemetry.num_samples - vec.telemetry.num_samples) <= 2

    def test_routed_deltas_reconcile_with_counters(self, routed):
        """Per-window deltas must sum to the run's end-of-run counters on
        each backend independently — no events lost between windows."""
        for backend, res in routed.items():
            tel = res.telemetry
            for fam, total in (
                ("preemptions", res.preemptions),
                ("rejections", res.rejections),
                ("truncations", res.truncations),
            ):
                sampled = sum(
                    tel.column(f"{fam}.{p}").sum() for p in tel.pool_names
                )
                assert sampled == total, (backend, fam)
            assert tel.column("spills").sum() == res.summary.spills, backend

    def test_routed_series_close(self, routed):
        """Cross-backend: the sampled error mass agrees within the routed
        tolerance (routing staleness shifts individual windows)."""
        ref, vec = routed["reference"], routed["vectorized"]
        for fam in ("preemptions", "truncations"):
            a = sum(
                ref.telemetry.column(f"{fam}.{p}").sum()
                for p in ref.telemetry.pool_names
            )
            b = sum(
                vec.telemetry.column(f"{fam}.{p}").sum()
                for p in vec.telemetry.pool_names
            )
            assert b == pytest.approx(a, rel=0.25, abs=20), fam

    def test_routed_exports_validate(self, routed):
        from repro.obs import (
            validate_chrome_trace,
            validate_events_jsonl,
            validate_telemetry,
        )

        for res in routed.values():
            validate_telemetry(res.telemetry.to_json())
            validate_events_jsonl(res.telemetry.events.to_jsonl())
            validate_chrome_trace(res.telemetry.events.to_chrome_trace())

    def test_threshold_column_tracks_controller(self):
        """The sampled ``threshold.0`` series replays the controller's
        move history exactly (post-move vector at each window)."""
        from repro.core.adaptive import AdaptiveController
        from repro.obs import TelemetryConfig

        n, rate = 2500, 250.0
        cols = generate_trace_columns(
            TraceSpec(trace="azure", num_requests=n, rate=rate, seed=42)
        )
        plan = plan_fleet("azure", cols.to_requests(), A100_LLAMA3_70B, rate)
        pools = {
            "short": (
                PoolConfig(
                    "short", 8192, n_seq_for_cmax(8192),
                    headroom=1.05, queue_limit=64,
                ),
                max(1, int(plan.short.instances * 0.6)),
            ),
            "long": (
                PoolConfig("long", 65_536, 16, headroom=1.02, queue_limit=64),
                plan.long.instances,
            ),
        }
        ctrl = AdaptiveController(b_min=512)
        sim = FleetSim(
            dict(pools), A100_LLAMA3_70B, b_short=8192, backend="vectorized",
            controller=ctrl, control_window=200,
            telemetry=TelemetryConfig(window=200, events=True),
        )
        res = sim.run(cols)
        assert ctrl.history  # the incident actually fired the controller
        tel = res.telemetry
        t_req = tel.column("t_req")
        th = tel.column("threshold.0")
        # replay: threshold at window [.., hi) is the vector after every
        # move with boundary index <= hi
        moves = {m.t: m.value for m in ctrl.history}
        expect, cur = [], 8192
        for hi in t_req:
            cur = moves.get(int(hi), cur)
            expect.append(cur)
        assert th.tolist() == expect
        # every move also landed in the event trace on the router track
        ev = [e for e in tel.events.events() if e["kind"] == "threshold_move"]
        assert len(ev) == len(ctrl.history)
        assert all(e["pool"] == "router" for e in ev)


class TestFaultEquivalence:
    """Fault semantics are backend-invariant (PR 7 acceptance classes).

    Single pool + dyadic timing + ``coalesce_dt=0`` keeps fault application
    bit-exact: identical SimSummary fields, fault counters, availability,
    per-request pool records, and fleet-level failure records for every
    fault kind and recovery path.
    """

    def _run(self, trace, backend, specs, policy=None, instances=4):
        from repro.sim.faults import FaultInjector

        cfg = PoolConfig("p", 4096, 16)
        sim = FleetSim(
            {cfg.name: (cfg, instances)},
            DYADIC,
            backend=backend,
            coalesce_dt=0.0,
            injector=FaultInjector(specs),
            retry_policy=policy,
        )
        return sim, sim.run(trace)

    def _assert_equal(self, trace, specs, policy=None, instances=4):
        ref_sim, ref = self._run(trace, "reference", specs, policy, instances)
        vec_sim, vec = self._run(trace, "vectorized", specs, policy, instances)
        for f in SUMMARY_FIELDS:
            assert getattr(ref.summary, f) == getattr(vec.summary, f), f
        for f in ("retries", "timeouts", "shed", "instance_failures"):
            assert getattr(ref, f) == getattr(vec, f), f
        assert ref.availability == vec.availability
        ref_pool = sorted(
            (r.request_id, r.arrival, r.first_token, r.finish,
             r.output_tokens, r.preemptions, r.truncated, r.rejected)
            for p in ref_sim.pools.values() for r in p.records
        )
        vec_pool = sorted(
            (r.request_id, r.arrival, r.first_token, r.finish,
             r.output_tokens, r.preemptions, r.truncated, r.rejected)
            for p in vec_sim.pools.values() for r in p.records
        )
        assert ref_pool == vec_pool
        ref_fail = sorted((r.request_id, r.arrival, r.finish) for r in ref.fail_records)
        vec_fail = sorted((r.request_id, r.arrival, r.finish) for r in vec.fail_records)
        assert ref_fail == vec_fail
        return ref, vec

    def test_crash_requeue(self):
        from repro.sim.faults import FaultSpec

        trace = poisson_trace(500, rate=250.0, seed=21)
        ref, _ = self._assert_equal(
            trace,
            (FaultSpec("crash", "p", instance=1, t=0.5, duration=0.25, requeue=True),),
        )
        assert ref.instance_failures == 1 and ref.availability < 1.0

    def test_crash_lost_with_retries(self):
        from repro.sim.faults import FaultSpec, RetryPolicy

        trace = poisson_trace(500, rate=250.0, seed=22)
        pol = RetryPolicy(
            max_retries=3, base_backoff=2**-6, max_backoff=2**-3, jitter=0.25, seed=1
        )
        ref, _ = self._assert_equal(
            trace,
            (FaultSpec("crash", "p", instance=0, t=0.5, duration=0.25),),
            policy=pol,
        )
        assert ref.retries > 0

    def test_crash_with_warmup_degradation(self):
        from repro.sim.faults import FaultSpec

        trace = poisson_trace(500, rate=250.0, seed=23)
        self._assert_equal(
            trace,
            (
                FaultSpec(
                    "crash", "p", instance=2, t=0.5, duration=0.25,
                    requeue=True, warmup=0.25, warmup_factor=2.0,
                ),
            ),
        )

    def test_oom_kill_both_dispositions(self):
        from repro.sim.faults import FaultSpec, RetryPolicy

        trace = poisson_trace(500, rate=300.0, seed=24)
        self._assert_equal(
            trace,
            (FaultSpec("oom", "p", instance=1, t=0.5, evict_frac=0.5, requeue=True),),
        )
        pol = RetryPolicy(max_retries=2, base_backoff=2**-6, max_backoff=2**-4, jitter=0.0)
        ref, _ = self._assert_equal(
            trace,
            (FaultSpec("oom", "p", instance=1, t=0.5, evict_frac=0.75),),
            policy=pol,
        )
        assert ref.retries > 0

    def test_slowdown_dyadic_factor(self):
        from repro.sim.faults import FaultSpec

        trace = poisson_trace(500, rate=250.0, seed=25)
        # dyadic factors keep t_iter * factor an exact binary float in both
        # the scalar multiply and the masked vector multiply
        for factor in (2.0, 1.5):
            self._assert_equal(
                trace,
                (FaultSpec("slowdown", "p", instance=0, t=0.25, duration=0.5,
                           factor=factor),),
            )

    def test_timeout_drops(self):
        from repro.sim.faults import FaultSpec, RetryPolicy

        trace = poisson_trace(400, rate=200.0, seed=26)
        pol = RetryPolicy(
            max_retries=5, base_backoff=2**-2, max_backoff=2.0, jitter=0.0,
            timeout=0.25,
        )
        ref, _ = self._assert_equal(
            trace,
            (FaultSpec("crash", "p", instance=0, t=0.5, duration=0.5),),
            policy=pol,
        )
        assert ref.timeouts > 0 and len(ref.fail_records) == ref.timeouts

    def test_overlapping_fault_storm(self):
        """Several faults on several instances, interleaved in time."""
        from repro.sim.faults import FaultSpec, RetryPolicy

        trace = poisson_trace(600, rate=300.0, seed=27)
        specs = (
            FaultSpec("crash", "p", instance=0, t=0.25, duration=0.25),
            FaultSpec("slowdown", "p", instance=1, t=0.375, duration=0.25, factor=2.0),
            FaultSpec("oom", "p", instance=2, t=0.5, evict_frac=0.5, requeue=True),
            FaultSpec("crash", "p", instance=3, t=0.625, duration=0.125, requeue=True),
        )
        pol = RetryPolicy(max_retries=2, base_backoff=2**-6, max_backoff=2**-4, jitter=0.5, seed=9)
        ref, _ = self._assert_equal(trace, specs, policy=pol)
        assert ref.instance_failures == 3


# ---------------------------------------------------------------------------
# Third backend: jitted jax event loop + vmapped grids
# ---------------------------------------------------------------------------


class TestJaxBackendEquivalence:
    """``backend="jax"`` joins the backend-equivalence contract: the
    compiled event loop must be *bit-identical* to both host engines on the
    exact class (routerless single pool, dyadic timing, ``coalesce_dt=0``)
    — including the adversarial KV-pressure trace that drives the shared
    order-free preemption rule hard."""

    def _triple(self, trace, cfg, instances, *, total_blocks=None):
        out = {}
        for backend in ("reference", "vectorized", "jax"):
            sim, res = run_single_pool(
                trace, cfg, instances, backend, total_blocks=total_blocks
            )
            out[backend] = (sim, res)
        return out

    def test_basic_three_way_identical(self):
        cfg = PoolConfig("p", 4096, 16)
        trace = poisson_trace(600, 220.0, 7, l_in=(16, 1200), l_out=(1, 200))
        runs = self._triple(trace, cfg, 3)
        ref_tuples = record_tuples(*reversed(runs["reference"]))
        for backend in ("vectorized", "jax"):
            sim, res = runs[backend]
            assert record_tuples(res, sim) == ref_tuples, backend
            for f in SUMMARY_FIELDS:
                assert getattr(res.summary, f) == getattr(
                    runs["reference"][1].summary, f
                ), (backend, f)

    def test_kv_pressure_three_way_identical(self):
        """Preemption/truncation heavy: tiny block pool forces constant
        victim selection; all three backends must agree bit-for-bit."""
        cfg = PoolConfig("p", 1024, 8)
        trace = poisson_trace(500, 400.0, 3, l_in=(16, 900), l_out=(1, 400))
        runs = self._triple(trace, cfg, 3, total_blocks=90)
        ref_sim, ref = runs["reference"]
        assert ref.preemptions > 100  # the trace exercises the hard path
        assert ref.summary.truncated > 50
        ref_tuples = record_tuples(ref, ref_sim)
        for backend in ("vectorized", "jax"):
            sim, res = runs[backend]
            assert record_tuples(res, sim) == ref_tuples, backend
            assert res.preemptions == ref.preemptions
            assert res.truncations == ref.truncations

    def test_submit_rejects_identical(self):
        cfg = PoolConfig("p", 1024, 8)  # prompts ≥ 1024 → submit-time reject
        trace = poisson_trace(300, 200.0, 5, l_in=(16, 2000), l_out=(1, 100))
        runs = self._triple(trace, cfg, 2)
        ref_tuples = record_tuples(*reversed(runs["reference"]))
        assert runs["reference"][1].rejections > 0
        for backend in ("vectorized", "jax"):
            sim, res = runs[backend]
            assert record_tuples(res, sim) == ref_tuples, backend
            assert res.rejections == runs["reference"][1].rejections

    def test_telemetry_windows_identical(self):
        """Replayed device window snapshots must reproduce the host
        backend's windowed time series exactly on the exact class."""
        from repro.obs import TelemetryConfig

        cfg = PoolConfig("p", 4096, 16)
        trace = poisson_trace(1500, 250.0, 11)
        tel = TelemetryConfig(window=100, events=False)
        res = {}
        for backend in ("vectorized", "jax"):
            sim = FleetSim(
                {"p": (cfg, 4)},
                DYADIC,
                backend=backend,
                coalesce_dt=0.0,
                telemetry=tel,
                control_window=100,
            )
            res[backend] = sim.run(trace)
        v, j = res["vectorized"].telemetry, res["jax"].telemetry
        assert v.num_samples == j.num_samples > 0
        assert set(v.columns) == set(j.columns)
        for name in v.columns:
            assert np.array_equal(
                v.column(name), j.column(name), equal_nan=True
            ), name

    def test_jax_rejects_fault_injection(self):
        from repro.sim.faults import FaultInjector, FaultSpec

        cfg = PoolConfig("p", 4096, 16)
        inj = FaultInjector((FaultSpec("crash", "p", instance=0, t=0.5),))
        with pytest.raises(ValueError, match="fault injection"):
            FleetSim({"p": (cfg, 2)}, DYADIC, backend="jax", injector=inj)

    def test_jax_rejects_event_tracing(self):
        from repro.obs import TelemetryConfig

        cfg = PoolConfig("p", 4096, 16)
        with pytest.raises(ValueError, match="event tracing"):
            FleetSim(
                {"p": (cfg, 2)},
                DYADIC,
                backend="jax",
                telemetry=TelemetryConfig(window=64, events=True),
            )


class TestJaxRecordTrips:
    """The compiled loop writes a round's completion records in trips of
    ``_REC_TRIP_ROWS`` (32) rows. A burst of three requests for each of
    40 instances, each of which finishes one request a round, completes
    40 requests in each of its three rounds: each round needs a second,
    part-filled trip. The records must equal the vectorized engine's,
    lane by lane."""

    POOLS = {
        "short": (PoolConfig("short", 2048, 4), 24),
        "long": (PoolConfig("long", 8192, 4), 16),
    }
    THRESHOLDS = (2048, 1024)  # both route the 40 large prompts long

    @staticmethod
    def burst(n=120):
        return [
            Request(
                request_id=i,
                byte_len=40_000 if i % 5 < 2 else 8,
                max_output_tokens=1,
                category=0,
                arrival_time=0.0,
                true_input_tokens=1,
                true_output_tokens=1,
            )
            for i in range(n)
        ]

    @pytest.mark.parametrize("mode", ["single", "grid"])
    def test_burst_records_match_across_trips(self, mode):
        from collections import Counter

        from repro.sim import jax_engine

        trace = self.burst()
        lanes = self.THRESHOLDS if mode == "grid" else self.THRESHOLDS[:1]
        want = []
        for th in lanes:
            sim = FleetSim(dict(self.POOLS), DYADIC, backend="vectorized",
                           coalesce_dt=0.0, spillover=False, thresholds=[th])
            want.append(record_tuples(sim.run(trace), sim))
        if mode == "single":
            sim = FleetSim(dict(self.POOLS), DYADIC, backend="jax",
                           spillover=False, thresholds=list(lanes))
            got = [record_tuples(sim.run(trace), sim)]
        else:
            grid = jax_engine.run_fleet_grid(
                trace, dict(self.POOLS), DYADIC,
                thresholds=[[th] for th in lanes], return_records=True)
            rec = grid.records
            got = [
                sorted(
                    (r.request_id, r.arrival_time, rec["first"][k, j],
                     rec["finish"][k, j], int(rec["out"][k, j]),
                     int(rec["pre"][k, j]), bool(rec["trunc"][k, j]),
                     bool(rec["rej"][k, j]))
                    for j, r in enumerate(trace)
                )
                for k in range(len(lanes))
            ]
        assert got == want
        # every instance finishes one request a round, at one time
        per_round = [Counter(t[3] for t in w) for w in want]
        assert sorted(per_round[0].values()) == [40, 40, 40]
        K = jax_engine._REC_TRIP_ROWS
        trips = max(sum(-(-c // K) for c in pr.values()) for pr in per_round)
        stats = jax_engine.last_run_stats()
        assert stats["mode"] == ("grid" if mode == "grid" else "fleet")
        assert stats["rec_trips"] == trips == 6
        assert stats["rounds"] == 3


class TestJaxRoutedTolerance:
    """Routed fleets on the jax backend precompute EMA budgets host-side in
    arrival order (the device loop only does a searchsorted per dispatch),
    so routing is tolerance-equivalent to the host backends — same contract
    the vectorized backend has vs the reference engine. Spillover is not
    modeled on-device, so the host comparator runs with spillover off."""

    @pytest.fixture(scope="class")
    def results(self):
        n, rate = 4000, 400.0
        trace = generate_trace(
            TraceSpec(trace="azure", num_requests=n, rate=rate, seed=42)
        )
        plan = plan_fleet("azure", trace, A100_LLAMA3_70B, rate)
        pools = {
            "short": (
                PoolConfig("short", 8192, n_seq_for_cmax(8192), headroom=1.05),
                plan.short.instances,
            ),
            "long": (
                PoolConfig("long", 65_536, 16, headroom=1.02),
                plan.long.instances,
            ),
        }
        vec = run_fleet(
            trace, pools, A100_LLAMA3_70B, backend="vectorized", spillover=False
        )
        jx = run_fleet(
            trace, pools, A100_LLAMA3_70B, backend="jax", spillover=False
        )
        return vec, jx

    def test_completion_totals_close(self, results):
        vec, jx = results
        assert jx.summary.num_requests == vec.summary.num_requests
        assert jx.summary.completed == pytest.approx(
            vec.summary.completed, rel=0.01
        )

    def test_latency_percentiles_close(self, results):
        vec, jx = results
        assert jx.summary.ttft_p99 == pytest.approx(
            vec.summary.ttft_p99, rel=0.15
        )
        assert jx.summary.tpot_p99 == pytest.approx(
            vec.summary.tpot_p99, rel=0.15
        )

    def test_routing_fractions_close(self, results):
        vec, jx = results
        for name, frac in vec.router_stats["fractions"].items():
            assert jx.router_stats["fractions"][name] == pytest.approx(
                frac, abs=0.02
            ), name

    def test_every_request_accounted(self, results):
        vec, jx = results
        # every submitted request got exactly one routing decision, on both
        # backends (the summaries themselves discard the 20% warm-up)
        assert sum(jx.router_stats["routed"].values()) == 4000
        assert sum(vec.router_stats["routed"].values()) == 4000


class TestFleetGrid:
    """``run_fleet_grid`` vmaps whole fleet runs across threshold /
    instance-count / controller-gain axes. A grid lane must be bit-identical
    to the same configuration run through ``FleetSim(backend="jax")`` — the
    vmap axis cannot perturb the simulation."""

    @pytest.fixture(scope="class")
    def setup(self):
        from repro.sim.jax_engine import run_fleet_grid

        n, rate = 2000, 400.0
        trace = generate_trace(
            TraceSpec(trace="azure", num_requests=n, rate=rate, seed=42)
        )
        plan = plan_fleet("azure", trace, A100_LLAMA3_70B, rate)
        pools = {
            "short": (
                PoolConfig("short", 8192, n_seq_for_cmax(8192), headroom=1.05),
                plan.short.instances,
            ),
            "long": (
                PoolConfig("long", 65_536, 16, headroom=1.02),
                plan.long.instances,
            ),
        }
        grid = run_fleet_grid(
            trace,
            pools,
            A100_LLAMA3_70B,
            thresholds=[[2048], [4096], [8192]],
            return_records=True,
        )
        return trace, pools, grid

    def test_grid_lane_matches_single_run(self, setup):
        trace, pools, grid = setup
        sim = FleetSim(
            dict(pools), A100_LLAMA3_70B, backend="jax", spillover=False
        )
        res = sim.run(trace)
        k = 2  # thresholds [8192] == FleetSim's default b_short boundary
        single = {}
        for p in sim.pools.values():
            a = p.record_arrays()
            for j in range(len(a["request_id"])):
                single[int(a["request_id"][j])] = (
                    a["first_token"][j],
                    a["finish"][j],
                    int(a["output_tokens"][j]),
                    int(a["preemptions"][j]),
                    bool(a["truncated"][j]),
                    bool(a["rejected"][j]),
                )
        order = np.argsort([r.arrival_time for r in trace], kind="stable")
        ids = np.array([r.request_id for r in trace])[order]
        rec = grid.records
        for j, rid in enumerate(ids):
            got = (
                rec["first"][k, j],
                rec["finish"][k, j],
                int(rec["out"][k, j]),
                int(rec["pre"][k, j]),
                bool(rec["trunc"][k, j]),
                bool(rec["rej"][k, j]),
            )
            assert got == single[int(rid)], rid
        assert int(grid.routed[k, 0]) == res.router_stats["routed"]["short"]

    def test_threshold_axis_is_monotone_in_routing(self, setup):
        _, _, grid = setup
        # raising the boundary can only move requests short-ward
        short = grid.routed[:, 0]
        assert (np.diff(short) >= 0).all()
        assert (grid.routed.sum(axis=1) == len(grid.records["rej"][0])).all()

    def test_instance_and_gain_axes(self, setup):
        from repro.sim.jax_engine import run_fleet_grid

        trace, pools, _ = setup
        base = [ni for _, (_, ni) in sorted(
            pools.items(), key=lambda kv: kv[1][0].c_max
        )]
        shrunk = [max(1, base[0] - 2), base[1]]
        grid = run_fleet_grid(
            trace,
            pools,
            A100_LLAMA3_70B,
            thresholds=[[4096]],
            instances=[base, shrunk],
            gains=[None, {"decrease_factor": 0.5}],
        )
        assert len(grid) == 2
        # fewer instances → no more completions than the full fleet
        assert grid.completed[1] <= grid.completed[0]
        # uncontrolled lane never moves; controlled lane stays clamped
        assert grid.controller_moves[0] == 0
        assert (grid.final_thresholds[0] == 4096).all()
        b_min, c_max_short = 512, 8192
        assert b_min <= int(grid.final_thresholds[1][0]) <= c_max_short

    def test_bad_axis_length_raises(self, setup):
        from repro.sim.jax_engine import run_fleet_grid

        trace, pools, _ = setup
        with pytest.raises(ValueError, match="grid axis"):
            run_fleet_grid(
                trace,
                pools,
                A100_LLAMA3_70B,
                thresholds=[[2048], [4096]],
                gains=[None, None, None],
            )

    def test_grid_summaries_come_from_the_records(self):
        """Every per-lane number of a grid is a summary of its records:
        counts and makespan equal those recomputed from the records, and
        the latency means and percentiles equal ``jnp.nanmean`` /
        ``jnp.nanpercentile`` over them under x64, to float64 rounding."""
        import jax
        import jax.numpy as jnp

        from repro.sim.jax_engine import run_fleet_grid

        trace = poisson_trace(300, 220.0, 11, l_in=(16, 4000), l_out=(1, 200))
        pools = {
            "short": (PoolConfig("short", 2048, 8), 2),
            "long": (PoolConfig("long", 8192, 8), 2),
        }
        # a boundary of 1 sends every request long (no prompt is over its
        # C_max); 8191 sends most short, where long prompts are rejected
        grid = run_fleet_grid(trace, pools, DYADIC,
                              thresholds=[[1], [8191]], return_records=True)
        rec = grid.records
        arr = np.sort([r.arrival_time for r in trace])
        assert grid.rejected[0] == 0 < grid.rejected[1]
        for k in range(2):
            rej = rec["rej"][k]
            assert grid.completed[k] == np.sum(~rej)
            assert grid.rejected[k] == np.sum(rej)
            assert grid.truncated[k] == np.sum(rec["trunc"][k])
            assert grid.routed[k].tolist() == [
                int(np.sum(rec["pool"][k] == p)) for p in range(2)]
            assert grid.makespan[k] == np.max(rec["finish"][k]) - arr[0]
            with jax.enable_x64():
                first = jnp.asarray(rec["first"][k])
                finish = jnp.asarray(rec["finish"][k])
                out = jnp.asarray(rec["out"][k])
                done = jnp.asarray(~rej)
                ttft = jnp.where(done, first - jnp.asarray(arr), jnp.nan)
                tpot = jnp.where(done & (out > 1),
                                 (finish - first) / jnp.maximum(out - 1, 1),
                                 jnp.nan)
                want = {
                    "ttft_mean": jnp.nanmean(ttft),
                    "ttft_p50": jnp.nanpercentile(ttft, 50),
                    "ttft_p99": jnp.nanpercentile(ttft, 99),
                    "tpot_mean": jnp.nanmean(tpot),
                    "tpot_p99": jnp.nanpercentile(tpot, 99),
                }
                want = {f: float(v) for f, v in want.items()}
            for field, value in want.items():
                assert np.isfinite(value), field
                assert getattr(grid, field)[k] == pytest.approx(
                    value, rel=1e-12), (k, field)


class TestKernelCaching:
    """Routing/observe kernel specializations are cached by ``(name, …)``
    keys; a second run with the same shapes must not retrace anything."""

    def test_no_retrace_on_second_run(self):
        from repro.core.calibration import kernel_trace_counts

        cfg_s = PoolConfig("short", 8192, n_seq_for_cmax(8192), headroom=1.05)
        cfg_l = PoolConfig("long", 65_536, 16, headroom=1.02)
        trace = generate_trace(
            TraceSpec(trace="azure", num_requests=800, rate=200.0, seed=9)
        )
        pools = {"short": (cfg_s, 2), "long": (cfg_l, 2)}

        def one_run():
            return run_fleet(
                trace, pools, A100_LLAMA3_70B, backend="vectorized"
            )

        one_run()
        before = kernel_trace_counts()
        one_run()
        after = kernel_trace_counts()
        assert before  # kernels were exercised at all
        assert after == before  # …and never retraced


class TestDonatedBufferParity:
    """The compiled entries donate their record buffers
    (``donate_argnums``): every call allocates a fresh set via
    ``_fresh_records`` and the in-loop scatters write into them, so
    results must never depend on buffer history. Repeated runs and
    interleaved records/summary grid calls have to stay bit-identical —
    a stale or reused donated buffer would leak one run's completions
    into the next."""

    @pytest.fixture(scope="class")
    def fixture(self):
        cfg = PoolConfig("p", 4096, 16)
        trace = poisson_trace(400, 220.0, 13, l_in=(16, 1200), l_out=(1, 200))
        return cfg, trace

    def test_repeated_runs_bit_identical(self, fixture):
        cfg, trace = fixture
        base = None
        for _ in range(3):
            sim, res = run_single_pool(trace, cfg, 3, "jax")
            tuples = record_tuples(res, sim)
            if base is None:
                base = tuples
            assert tuples == base

    def test_interleaved_grid_record_modes(self, fixture):
        from repro.sim.jax_engine import compile_stats, run_fleet_grid

        _, trace = fixture
        pools = {
            "short": (PoolConfig("short", 2048, 8), 2),
            "long": (PoolConfig("long", 8192, 8), 2),
        }
        thresholds = [[512], [1536]]

        def grid(return_records):
            return run_fleet_grid(
                trace,
                pools,
                DYADIC,
                thresholds=thresholds,
                return_records=return_records,
            )

        rows0 = len(compile_stats())
        with_rec = grid(True)
        summary_only = grid(False)
        again = grid(True)
        # both record modes ran one executable
        new = [r for r in compile_stats()[rows0:]
               if r["grid"] and (r["n"], r["g"]) == (len(trace), 2)]
        assert len(new) == 1
        assert summary_only.records is None
        assert (with_rec.completed == summary_only.completed).all()
        assert (with_rec.completed == again.completed).all()
        for k, v in with_rec.records.items():
            assert np.array_equal(v, again.records[k], equal_nan=True), k


class TestCoalescedJumpEquivalence:
    """Event-coalesced k-jumps inside the compiled loop: the outer
    while iterates once per arrival epoch (fleet mode), so the surfaced
    iteration counter is bounded by n + 1 while rounds stay far below
    the token count a step-per-token loop would need — and coalescing
    must not perturb exact-class equivalence with either host engine."""

    def test_iters_bounded_and_exact(self):
        from repro.sim import jax_engine

        cfg = PoolConfig("p", 4096, 16)
        trace = poisson_trace(600, 220.0, 7, l_in=(16, 1200), l_out=(1, 200))
        runs = {}
        for backend in ("reference", "vectorized", "jax"):
            sim, res = run_single_pool(trace, cfg, 3, backend)
            runs[backend] = record_tuples(res, sim)
        assert runs["jax"] == runs["reference"] == runs["vectorized"]

        stats = jax_engine.last_run_stats()
        assert stats["mode"] == "fleet"
        n = len(trace)
        assert 0 < stats["iters"] <= n + 1
        total_tokens = sum(t[4] for t in runs["jax"])  # output_tokens
        assert stats["rounds"] >= stats["iters"]
        # coalesced jumps: rounds ≪ one-round-per-generated-token
        assert stats["rounds"] < total_tokens / 5

    def test_grid_iters_bounded(self):
        from repro.sim import jax_engine
        from repro.sim.jax_engine import run_fleet_grid

        trace = poisson_trace(300, 220.0, 3, l_in=(16, 1200), l_out=(1, 150))
        pools = {
            "short": (PoolConfig("short", 2048, 8), 2),
            "long": (PoolConfig("long", 8192, 8), 2),
        }
        run_fleet_grid(trace, pools, DYADIC, thresholds=[[512], [1536]])
        stats = jax_engine.last_run_stats()
        assert stats["mode"] == "grid"
        # grid lanes run one unconditional round per outer iteration, so
        # the iteration counter equals the slowest lane's round count and
        # the totals surface per-lane sums for benchmarking.
        assert stats["rounds"] == stats["iters"]
        assert stats["rounds_total"] <= stats["rounds"] * 2
