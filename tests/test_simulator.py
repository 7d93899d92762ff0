"""Discrete-event simulator: conservation, reliability, paper anchors."""

import hypothesis.strategies as st
import pytest
from hypothesis import given, settings

from repro.core.pools import PoolConfig, n_seq_for_cmax
from repro.core.router import Request
from repro.sim import (
    A100_LLAMA3_70B,
    InstanceSim,
    TimingModel,
    plan_fleet,
    run_fleet,
)
from repro.traces import TraceSpec, generate_trace

settings.register_profile("fast", max_examples=20, deadline=None)
settings.load_profile("fast")

FAST = TimingModel("fast", w_base=1e-3, h_per_seq=1e-4, prefill_chunk=512)


def mk_request(i, t, l_in, l_out):
    return Request(
        request_id=i,
        byte_len=l_in * 4,
        max_output_tokens=l_out,
        category=0,
        arrival_time=t,
        true_input_tokens=l_in,
        true_output_tokens=l_out,
    )


class TestInstanceSim:
    def test_single_request_completes(self):
        pool = PoolConfig("p", 4096, 4)
        inst = InstanceSim(pool, FAST)
        inst.submit(mk_request(0, 0.0, 600, 5), 0.0)
        t = 0.0
        for _ in range(100):
            dt, done = inst.step(t)
            t += max(dt, 1e-9)
            if done:
                break
        assert len(inst.records) == 1
        rec = inst.records[0]
        assert rec.output_tokens == 5
        # 600 tokens → 2 prefill chunks; first token in chunk-2's iteration
        assert rec.first_token > 0
        assert rec.finish >= rec.first_token

    def test_reject_oversized_prompt(self):
        pool = PoolConfig("p", 1024, 4)
        inst = InstanceSim(pool, FAST)
        ok = inst.submit(mk_request(0, 0.0, 2000, 5), 0.0)
        assert not ok
        assert inst.rejection_count == 1
        assert inst.records[0].rejected

    def test_truncation_at_cmax(self):
        pool = PoolConfig("p", 128, 2)
        inst = InstanceSim(pool, FAST)
        inst.submit(mk_request(0, 0.0, 100, 1000), 0.0)
        t = 0.0
        for _ in range(2000):
            dt, done = inst.step(t)
            t += max(dt, 1e-9)
            if done:
                break
        rec = inst.records[0]
        assert rec.truncated
        assert 100 + rec.output_tokens <= 128

    def test_block_accounting_never_negative(self):
        pool = PoolConfig("p", 2048, 8)
        inst = InstanceSim(pool, FAST)
        for i in range(20):
            inst.submit(mk_request(i, 0.0, 500, 50), 0.0)
        t = 0.0
        for _ in range(3000):
            assert 0 <= inst.blocks_free <= inst.total_blocks
            dt, _ = inst.step(t)
            if inst.idle:
                break
            t += max(dt, 1e-9)
        assert len([r for r in inst.records if not r.rejected]) == 20
        assert inst.blocks_free == inst.total_blocks  # all freed

    def test_preemption_under_block_pressure(self):
        """Tiny block budget + growing decodes → vLLM-style preemption."""
        pool = PoolConfig("p", 4096, 8, kv_blocks=80)
        inst = InstanceSim(pool, FAST)
        for i in range(8):
            inst.submit(mk_request(i, 0.0, 64, 400), 0.0)
        t = 0.0
        for _ in range(20_000):
            dt, _ = inst.step(t)
            if inst.idle:
                break
            t += max(dt, 1e-9)
        done = [r for r in inst.records if not r.rejected]
        assert len(done) == 8  # everyone eventually finishes
        assert inst.preemption_count > 0  # but some were preempted

    @given(
        n=st.integers(1, 25),
        seed=st.integers(0, 100),
    )
    def test_conservation(self, n, seed):
        """Every submitted request is exactly once completed or rejected."""
        import numpy as np

        r = np.random.default_rng(seed)
        pool = PoolConfig("p", 2048, 4)
        inst = InstanceSim(pool, FAST)
        for i in range(n):
            inst.submit(
                mk_request(
                    i,
                    float(r.uniform(0, 0.1)),
                    int(r.integers(1, 3000)),
                    int(r.integers(1, 50)),
                ),
                0.0,
            )
        t = 0.0
        for _ in range(50_000):
            dt, _ = inst.step(t)
            if inst.idle:
                break
            t += max(dt, 1e-9)
        ids = sorted(rec.request_id for rec in inst.records)
        assert ids == list(range(n))
        for rec in inst.records:
            if not rec.rejected:
                assert rec.finish >= rec.first_token >= 0


class TestFleet:
    def test_designed_fleet_zero_preemptions(self):
        """§4.3: zero preemptions / rejections at designed sizes."""
        reqs = generate_trace(
            TraceSpec(trace="azure", num_requests=600, rate=50, seed=42)
        )
        plan = plan_fleet("azure", reqs, A100_LLAMA3_70B, 50.0)
        short_cfg = PoolConfig(
            "short", 8192, n_seq_for_cmax(8192), headroom=1.05
        )
        long_cfg = PoolConfig("long", 65_536, 16, headroom=1.02)
        res = run_fleet(
            reqs,
            {
                "short": (short_cfg, plan.short.instances),
                "long": (long_cfg, plan.long.instances),
            },
            A100_LLAMA3_70B,
        )
        assert res.preemptions == 0
        assert res.summary.success_rate == 1.0
        assert res.summary.meets_slo()

    def test_router_feedback_calibrates(self):
        reqs = generate_trace(
            TraceSpec(trace="azure", num_requests=400, rate=50, seed=1)
        )
        plan = plan_fleet("azure", reqs, A100_LLAMA3_70B, 50.0)
        short_cfg = PoolConfig("short", 8192, 128, headroom=1.05)
        long_cfg = PoolConfig("long", 65_536, 16, headroom=1.02)
        res = run_fleet(
            reqs,
            {
                "short": (short_cfg, plan.short.instances),
                "long": (long_cfg, plan.long.instances),
            },
            A100_LLAMA3_70B,
        )
        calib = res.router_stats["calibration"]
        assert all(c > 0 for c in calib["count"])  # every category observed

    def test_paper_table1_throughputs(self):
        """μ within 5% of Table 1: 3.0 / 13.5 / 0.4 (Azure)."""
        reqs = generate_trace(
            TraceSpec(trace="azure", num_requests=10_000, rate=1000, seed=42)
        )
        plan = plan_fleet("azure", reqs, A100_LLAMA3_70B, 1000.0)
        assert plan.homogeneous.mu == pytest.approx(3.0, rel=0.05)
        assert plan.short.mu == pytest.approx(13.5, rel=0.05)
        assert plan.long.mu == pytest.approx(0.385, rel=0.1)

    def test_paper_table2_savings(self):
        """Savings within 1pp of Table 2: 16.6% Azure / 38.5% LMSYS."""
        for trace, expected in (("azure", 0.166), ("lmsys", 0.385)):
            reqs = generate_trace(
                TraceSpec(trace=trace, num_requests=10_000, rate=1000, seed=42)
            )
            plan = plan_fleet(trace, reqs, A100_LLAMA3_70B, 1000.0)
            assert plan.savings == pytest.approx(expected, abs=0.01)


class TestSummarizeParity:
    """``summarize`` (record objects) and ``summarize_columns`` (arrays)
    must agree on degenerate inputs, where warm-up cuts, empty percentile
    sets, and zero makespans are easiest to get wrong in one path only."""

    @staticmethod
    def _to_columns(records):
        import numpy as np

        return {
            "request_id": np.array([r.request_id for r in records], dtype=np.int64),
            "arrival": np.array([r.arrival for r in records]),
            "first_token": np.array([r.first_token for r in records]),
            "finish": np.array([r.finish for r in records]),
            "output_tokens": np.array(
                [r.output_tokens for r in records], dtype=np.int64
            ),
            "preemptions": np.array(
                [r.preemptions for r in records], dtype=np.int64
            ),
            "truncated": np.array([r.truncated for r in records], dtype=bool),
            "rejected": np.array([r.rejected for r in records], dtype=bool),
        }

    def _assert_parity(self, records, **kw):
        from repro.sim.metrics import summarize, summarize_columns

        a = summarize("x", records, **kw)
        b = summarize_columns("x", self._to_columns(records), **kw)
        assert a == b

    def test_empty_trace(self):
        self._assert_parity([])

    def test_all_rejected(self):
        from repro.sim.metrics import RequestRecord

        records = [
            RequestRecord(
                request_id=i,
                pool="p",
                arrival=float(i),
                first_token=float(i),
                finish=float(i),
                output_tokens=0,
                rejected=True,
            )
            for i in range(10)
        ]
        self._assert_parity(records)
        from repro.sim.metrics import summarize

        s = summarize("x", records)
        assert s.completed == 0 and s.rejected == 8  # post 20% warm-up cut
        assert s.makespan == 0.0 and s.throughput == 0.0

    def test_all_truncated(self):
        from repro.sim.metrics import RequestRecord

        records = [
            RequestRecord(
                request_id=i,
                pool="p",
                arrival=float(i),
                first_token=float(i) + 0.5,
                finish=float(i) + 1.0,
                output_tokens=1,  # truncated after the first token: no TPOT
                truncated=True,
            )
            for i in range(10)
        ]
        self._assert_parity(records)
        from repro.sim.metrics import summarize

        s = summarize("x", records)
        assert s.truncated == s.completed == 8
        assert s.tpot_p50 == s.tpot_p99 == 0.0  # no multi-token requests
        assert s.ttft_p50 == 0.5

    def test_single_record(self):
        from repro.sim.metrics import RequestRecord

        self._assert_parity(
            [
                RequestRecord(
                    request_id=0,
                    pool="p",
                    arrival=0.0,
                    first_token=0.25,
                    finish=1.0,
                    output_tokens=4,
                )
            ]
        )

    def test_mixed_with_spills_and_warmup(self):
        from repro.sim.metrics import RequestRecord

        records = [
            RequestRecord(
                request_id=i,
                pool="p",
                arrival=float(i),
                first_token=float(i) + 0.1 * (i + 1),
                finish=float(i) + 1.0 + 0.05 * i,
                output_tokens=i % 5,
                preemptions=i % 3,
                truncated=(i % 4 == 0),
                rejected=(i % 7 == 0),
            )
            for i in range(23)
        ]
        self._assert_parity(records, warmup_frac=0.20, total_spills=6)
        self._assert_parity(records, warmup_frac=0.0, total_spills=0)
