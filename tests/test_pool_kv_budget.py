"""Per-pool KV block budgets (``PoolConfig.kv_blocks``).

Every engine serves an instance from ``PoolConfig.total_blocks``, which is
``min(kv_blocks, n_seq * ceil(c_max / 16))``; ``kv_blocks`` defaults to the
A100 instance's 65,536 blocks (App. A). These tests pin the budget, its
derivation for the paper's MI300X case study (§4.7), the timing that case
study's fleet runs on, and the agreement of the reference, vectorized and
compiled engines (single lane and grid) on tiny fleets whose budget binds
below the default and above it.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.core.cost_model import (
    A100_80G,
    LLAMA3_70B_KV,
    MI300X,
    QWEN3_235B_KV,
    mi300x_case_study,
)
from repro.core.pools import TOTAL_KV_BLOCKS, PoolConfig, validate_pools
from repro.core.router import Request
from repro.sim import MI300X_QWEN3, jax_engine
from repro.sim.fleet import FleetSim
from repro.sim.profiler import mean_iterations
from repro.sim.timing import A100_W_SHARE, TimingModel, from_operating_point
from repro.traces import TraceSpec, generate_trace

DYADIC = TimingModel("dyadic", w_base=2**-10, h_per_seq=2**-13, prefill_chunk=512)
#: Dyadic too, with prefill chunks of 8,192 tokens so that prompts of tens
#: of thousands of tokens prefill in a few iterations.
DYADIC_8K = TimingModel("dyadic-8k", w_base=2**-10, h_per_seq=2**-13,
                        prefill_chunk=8192)


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


def _records(sim, res):
    """Sorted record tuples of a FleetSim run, any backend."""
    if res.records is not None:
        recs = res.records
    else:
        recs = [r for p in sim.pools.values() for r in p.records]
    return sorted(
        (r.request_id, r.first_token, r.finish, r.output_tokens,
         r.preemptions, r.truncated, r.rejected)
        for r in recs
    )


def _grid_records(trace, grid):
    rec = grid.records
    return sorted(
        (r.request_id, rec["first"][0, j], rec["finish"][0, j],
         int(rec["out"][0, j]), int(rec["pre"][0, j]),
         bool(rec["trunc"][0, j]), bool(rec["rej"][0, j]))
        for j, r in enumerate(trace)
    )


# ---------------------------------------------------------------------------
# The budget
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("c_max, n_seq", [
    (8192, 128), (65_536, 16), (1024, 8), (4096, 16), (32_768, 169),
    (8192, 676),
])
def test_default_budget_is_the_global_one(c_max, n_seq):
    """Without the field a pool's budget is App. A's
    ``min(65,536, n_seq * ceil(c_max / 16))``, as every engine computed it."""
    cfg = PoolConfig("p", c_max, n_seq)
    assert cfg.kv_blocks == TOTAL_KV_BLOCKS
    assert cfg.total_blocks == min(TOTAL_KV_BLOCKS,
                                   n_seq * -(-c_max // 16))


def test_budget_is_capped_by_the_slots_reservation():
    assert PoolConfig("p", 1024, 8, kv_blocks=90).total_blocks == 90
    assert PoolConfig("p", 1024, 8, kv_blocks=10_000).total_blocks == 512
    big = PoolConfig("p", 32_768, 169, kv_blocks=346_536)
    assert big.total_blocks == 169 * 2048 == 346_112


def test_non_positive_budget_is_refused():
    with pytest.raises(ValueError):
        validate_pools([PoolConfig("p", 1024, 8, kv_blocks=0)])


def test_engines_read_the_budget_from_the_config():
    cfg = PoolConfig("p", 1024, 8, kv_blocks=90)
    ref = FleetSim({"p": (cfg, 2)}, DYADIC, backend="reference")
    assert {i.total_blocks for i in ref.pools["p"].instances} == {90}
    assert {i.blocks_free for i in ref.pools["p"].instances} == {90}
    vec = FleetSim({"p": (cfg, 2)}, DYADIC, backend="vectorized")
    assert vec.pools["p"].total_blocks == 90
    assert list(vec.pools["p"].blocks_free) == [90, 90]
    assert jax_engine._pool_spec("p", cfg, 2)[3] == 90


# ---------------------------------------------------------------------------
# The MI300X case study (§4.7, Table 5)
# ---------------------------------------------------------------------------


def test_case_study_block_budget():
    """133.425 GB of KV per GPU at 24,064 B a token (23.5 KB) holds
    346,536 blocks of 16 tokens: 676 slots at 8,192, 169 at 32,768."""
    assert QWEN3_235B_KV.kv_bytes_per_token_per_gpu() == 24_064
    blocks = QWEN3_235B_KV.kv_blocks(MI300X)
    assert blocks == 346_536
    assert blocks * 24_064 * 16 <= QWEN3_235B_KV.kv_budget_per_gpu(MI300X)
    cs = mi300x_case_study()
    assert cs.kv_blocks == blocks
    assert (blocks // 512, blocks // 2048) == (cs.n_seq_short,
                                               cs.n_seq_long) == (676, 169)
    # the A100 Llama-3-70B instance holds more than App. A's round 65,536
    assert LLAMA3_70B_KV.kv_blocks(A100_80G) == 77_056


def test_from_operating_point_inverts_throughput():
    t = from_operating_point("x", rate=1000.0, instances=40, n_slots=16,
                             mean_iters=300.0)
    assert t.throughput(300.0, 16) == pytest.approx(1000.0 / 40, rel=1e-12)
    assert t.w_base / t.iter_time(16) == pytest.approx(A100_W_SHARE)


def test_case_study_timing_constants():
    """``MI300X_QWEN3`` is ``from_operating_point`` at Table 5's
    homogeneous point (197 instances of 169 slots for 10,000 req/s) over
    the mean iterations of the 20,000 Azure requests (seed 42) that the
    ``fleet-azure-10k`` fleet is planned on; bit for bit."""
    reqs = generate_trace(TraceSpec(trace="azure", num_requests=20_000,
                                    rate=10_000.0, seed=42))
    mean_iters = mean_iterations(reqs, TimingModel("probe", 1e-3, 0.0))
    assert mean_iters == 279.47895
    t = from_operating_point(MI300X_QWEN3.name, rate=10_000.0,
                             instances=197, n_slots=169,
                             mean_iters=mean_iters)
    assert t == MI300X_QWEN3
    assert (t.w_base, t.h_per_seq, t.prefill_chunk) == (
        0.005181948407921241, 3.982589744236552e-05, 512)
    # a full short instance (676) and a full long one (169)
    assert t.iter_time(676) == pytest.approx(0.0321, abs=5e-5)
    assert t.iter_time(169) == pytest.approx(0.0119, abs=5e-5)


def test_table5_script_lines(capsys):
    """The Table 5 script calls ``from_operating_point`` and prints what it
    printed before: 676 / 169 slots, 197 -> 139 nodes by Eq. 7, 177 by
    Eq. 8."""
    from benchmarks import table5_mi300x

    out = table5_mi300x.run()
    lines = capsys.readouterr().out
    assert "n_seq_8k=676;n_seq_32k=169;ratio=4.0" in lines
    assert "nodes_homo=197;nodes_dual=139" in lines
    assert "nodes_dual=177;savings=0.102" in lines
    assert (out["nodes_dual_eq7"], out["nodes_dual_eq8"]) == (139, 177)
    assert out["timing"].w_base == pytest.approx(0.0049454, abs=1e-7)


# ---------------------------------------------------------------------------
# The engines agree under a per-pool budget
# ---------------------------------------------------------------------------

#: (pool, instances, timing, trace) per case:
#: ``binds`` — 90 blocks of the 512 that 8 slots of 1,024 tokens reserve,
#: so admission waits and growth preempts throughout;
#: ``over_65536`` — 70,000 blocks, above the default, for 128 slots of
#: 65,536 tokens: prompts of 8,000-16,000 tokens fill it to about 90
#: sequences, whose growth then preempts; the default budget would admit
#: fewer, so the records differ.
CASES = {
    "binds": (PoolConfig("p", 1024, 8, kv_blocks=90), 3, DYADIC,
              dict(n=300, rate=400.0, seed=3, l_in=(16, 900),
                   l_out=(1, 400))),
    "over_65536": (PoolConfig("p", 65_536, 128, kv_blocks=70_000), 1,
                   DYADIC_8K,
                   dict(n=120, rate=200.0, seed=5, l_in=(8_000, 16_000),
                        l_out=(800, 2_000))),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    cfg, inst, timing, spec = CASES[request.param]
    trace = _trace(spec["n"], spec["rate"], spec["seed"], spec["l_in"],
                   spec["l_out"])
    runs = {}
    for backend in ("reference", "vectorized", "jax"):
        sim = FleetSim({"p": (cfg, inst)}, timing, backend=backend,
                       coalesce_dt=0.0)
        res = sim.run(trace)
        runs[backend] = (res, _records(sim, res))
    stats = jax_engine.last_run_stats()
    grid = jax_engine.run_fleet_grid(trace, {"p": (cfg, inst)}, timing,
                                     return_records=True)
    default = FleetSim({"p": (dataclasses.replace(
        cfg, kv_blocks=TOTAL_KV_BLOCKS), inst)}, timing, backend="reference",
        coalesce_dt=0.0)
    default_res = default.run(trace)
    return (request.param, trace, runs, grid,
            _records(default, default_res), stats)


def test_engines_agree(case):
    name, trace, runs, grid, _, _ = case
    ref_res, want = runs["reference"]
    assert ref_res.preemptions > 0, name  # the budget binds
    for backend in ("vectorized", "jax"):
        res, got = runs[backend]
        assert got == want, (name, backend)
        assert res.preemptions == ref_res.preemptions
    assert _grid_records(trace, grid) == want, name
    assert int(grid.preemptions[0]) == ref_res.preemptions


def test_the_budget_changes_the_records(case):
    """Against the same fleet at the default 65,536 blocks, the budget
    is what moved the records."""
    name, _, runs, _, default, _ = case
    assert runs["reference"][1] != default, name


def test_fleet_without_the_field_is_unchanged():
    """A pool built without ``kv_blocks`` and one built with the default
    stated give the same spec, executable key and records."""
    trace = _trace(200, 220.0, 7, (16, 1200), (1, 200))
    cfgs = (PoolConfig("p", 4096, 16), PoolConfig("p", 4096, 16,
                                                  kv_blocks=TOTAL_KV_BLOCKS))
    out = []
    for cfg in cfgs:
        sim = FleetSim({"p": (cfg, 3)}, DYADIC, backend="jax",
                       coalesce_dt=0.0)
        res = sim.run(trace)
        out.append((_records(sim, res), jax_engine._pool_spec("p", cfg, 3)))
    assert out[0] == out[1]
    assert out[0][1] == ("p", 4096, 16, 4096, 3)


def test_kv_block_counter(case):
    """``kv_block_rounds`` sums blocks in use after admission over the
    rounds: positive, and at most the whole budget every round."""
    name, _, _, _, _, s = case
    cfg, inst, _, _ = CASES[name]
    assert s["mode"] == "fleet"
    assert s["real_kv_blocks"] == inst * cfg.total_blocks
    assert 0 < s["kv_block_rounds"] <= s["rounds_total"] * s["real_kv_blocks"]


def test_fleet_kv_blocks_must_fit_the_loop_counter():
    """The loop sums blocks in use in int32: a fleet of 2**31 blocks or
    more is refused when its program is built."""
    timing = TimingModel("t", 1e-3, 1e-4)
    rows = [("p", 65_536, 16_384, 2**30, 2)]
    with pytest.raises(ValueError, match="int32"):
        jax_engine._make_core(jax_engine._sim_spec(rows, timing, 0), 8)
    ok = [("p", 65_536, 16_384, 2**30 - 1, 2)]
    jax_engine._make_core(jax_engine._sim_spec(ok, timing, 0), 8)
