"""The MI300X case-study fleet cell: found by name, sized by the program's
own plan and memory math, its reference copy with per-pool KV budgets
tied to the original, the program against that copy where the budgets
bind, and a whole traced run of a tiny copy on the CPU."""

from __future__ import annotations

import dataclasses
import json
import math
import types

import numpy as np
import pytest

from bench import core, fleet_common
from bench.drivers import fleet_single
from bench.reference import fleet_des, fleet_des_kv
from bench.tests import tiny

CELL = "fleet-azure-10k.single"
FLEET_METRICS = ("fleet.rounds_per_req", "fleet.adm_waves_per_round",
                 "fleet.evict_pass_share", "fleet.live_slot_share",
                 "fleet.host_ms_per_call", "fleet.rec_trips_per_round",
                 "fleet.peak_live_share", "fleet.kv_used_share")


def _config():
    return json.loads(
        (core.BENCH_DIR / "configs" / "fleet-azure-10k.json").read_text())


def _traffic():
    return json.loads(
        (core.BENCH_DIR / "traffic" / "single-azure-10k.json").read_text())


def test_cell_resolves_with_its_fleet_metrics():
    bench = core.load_benchmark()
    cell = core.resolve(bench, CELL)
    assert cell.chips == 1
    assert cell.traffic["driver"] == "fleet_single_kv"
    assert cell.traffic["generator"] == "fleet_trace"
    assert cell.traffic["trace"] == "azure"
    assert cell.traffic["rate"] == 10_000.0
    assert cell.traffic["thresholds"] == [8192]
    assert cell.traffic["requests"] == cell.config["trace_requests"]
    assert cell.config["trace_requests"] % 10_000 == 0
    assert {m["name"] for m in cell.end_to_end} == {"sim_lane_req_per_s",
                                                    "setup_s"}
    assert set(FLEET_METRICS) <= set(cell.readers)
    entry = next(c for c in bench["configs"] if c["name"] == "fleet-azure-10k")
    assert set(entry["reduced"]) == set(cell.config["reduced"])
    sources = [c["source"] for c in bench["configs"]]
    assert len(set(sources)) == len(sources)


def test_pools_are_the_programs_plan():
    """48 short (8,192 x 676) and 133 long (32,768 x 169) instances:
    ``profile_pool`` at 10,000 req/s over 20,000 Azure requests drawn at
    seed 42, on the case study's timing and memory math."""
    from repro.core.cost_model import MI300X, QWEN3_235B_KV, mi300x_case_study
    from repro.core.pools import PoolConfig
    from repro.sim import MI300X_QWEN3
    from repro.sim.profiler import profile_pool, split_by_budget
    from repro.traces import TraceSpec, generate_trace

    cfg = _config()
    t = cfg["timing"]
    assert (t["name"], t["w_base"], t["h_per_seq"], t["prefill_chunk"]) == (
        MI300X_QWEN3.name, MI300X_QWEN3.w_base, MI300X_QWEN3.h_per_seq,
        MI300X_QWEN3.prefill_chunk)
    cs = mi300x_case_study()
    blocks = QWEN3_235B_KV.kv_blocks(MI300X)
    short, long_ = cfg["pools"]
    assert (short["n_seq"], long_["n_seq"]) == (cs.n_seq_short,
                                                cs.n_seq_long)
    assert short["kv_blocks"] == long_["kv_blocks"] == blocks == 346_536
    reqs = generate_trace(TraceSpec(trace="azure", num_requests=20_000,
                                    rate=10_000.0, seed=42))
    s_reqs, l_reqs = split_by_budget(reqs, 8192)
    plan = {}
    for p, sub in ((short, s_reqs), (long_, l_reqs)):
        pool = PoolConfig(p["name"], p["c_max"], p["n_seq"],
                          headroom=p["headroom"], kv_blocks=p["kv_blocks"])
        plan[p["name"]] = profile_pool(p["name"], reqs, sub, pool,
                                       MI300X_QWEN3, 10_000.0).instances
    assert (short["instances"], long_["instances"]) == (
        plan["short"], plan["long"]) == (48, 133)
    homo = profile_pool("homogeneous", reqs, reqs,
                        PoolConfig("homogeneous", 32_768, 169, headroom=1.08),
                        MI300X_QWEN3, 10_000.0)
    assert homo.instances == 213


#: A fleet cut small from the cell's configuration, one instance a pool,
#: loaded until its budgets bind: a short pool of 2,048 x 32 and a long one
#: of 32,768 x 512, whose slots reserve 4,096 and 1,048,576 blocks, under
#: 800 requests at 2,000 req/s split at 512 tokens. The long pool's c_max
#: rejects some prompts at submit and truncates some outputs, as the cell's.
STRESS_POOLS = [("short", 2048, 32, 1), ("long", 32_768, 512, 1)]
STRESS_SPLIT = [512]


def _stress(kv_blocks, n=800):
    cfg = _config()
    cfg["pools"] = [
        {"name": a, "c_max": c, "n_seq": s, "headroom": 1.0,
         "kv_blocks": kv, "instances": i}
        for (a, c, s, i), kv in zip(STRESS_POOLS, kv_blocks)]
    cfg["trace_requests"] = n
    traffic = {**_traffic(), "requests": n, "rate": 2000.0}
    return cfg, fleet_common.program_inputs(cfg, traffic, 2**33 + 3)


def test_reference_copy_is_the_original_at_the_default_budget():
    """With every budget at 65,536, the copy's records equal
    ``fleet_des``'s on a trace where that budget binds (it preempts)."""
    cfg, (cols, *_) = _stress([65_536, 65_536])
    want, _ = fleet_des.run_reference(cols, cfg, STRESS_SPLIT)
    got, _ = fleet_des_kv.run_reference(cols, cfg, STRESS_SPLIT)
    assert want["pre"].sum() > 0 and want["trunc"].sum() > 0
    assert want["rej"].sum() > 0
    for col, arr in want.items():
        np.testing.assert_array_equal(got[col], arr, err_msg=col)


def test_program_agrees_with_the_copy_where_budgets_bind():
    """Per-pool budgets below and above 65,536 (300 and 70,000 blocks),
    both preempting: the compiled tier, given them through
    ``PoolConfig.kv_blocks``, gives the copy's records, and in each pool
    they differ from the default budget's."""
    from repro.sim import FleetSim

    cfg, (cols, prog, pools, timing, cal) = _stress([300, 70_000])
    budget = {p["name"]: p["kv_blocks"] for p in cfg["pools"]}
    pools = {name: (dataclasses.replace(c, kv_blocks=budget[name]), n)
             for name, (c, n) in pools.items()}
    sim = FleetSim(pools, timing, backend="jax", spillover=False,
                   thresholds=STRESS_SPLIT, epoch=cfg["sim"]["epoch"],
                   calibrator=cal)
    sim.run(prog)
    # the records as the cell's driver reads them off the pool shells
    rec = fleet_single.Driver._records(types.SimpleNamespace(cols=cols), sim)
    ref, mismatch = fleet_des_kv.run_reference(cols, cfg, STRESS_SPLIT,
                                               program_pool=rec["pool"])
    assert mismatch == 0
    check = fleet_des_kv.compare_records(rec, ref)
    assert check["records_differing"] == 0
    assert check["time_gap_s"] <= cfg["checks"]["time_gap_s"]
    default, _ = fleet_des.run_reference(cols, cfg, STRESS_SPLIT)
    for pool in (0, 1):
        mine = ref["pool"] == pool
        assert ref["pre"][mine].sum() > 0, pool
        assert (ref["finish"][mine] != default["finish"][mine]).any(), pool


@pytest.mark.parametrize("stats, want", [
    ({"kv_block_rounds": 300, "rounds_total": 10, "real_kv_blocks": 60},
     50.0),
    ({"kv_block_rounds": 0, "rounds_total": 4, "real_kv_blocks": 60}, 0.0),
    ({"rounds_total": 8, "live_slot_rounds": 40}, None),  # no counter
    ({}, None),  # no run yet
])
def test_reader(monkeypatch, stats, want):
    from repro.sim import jax_engine

    reader = core.load_module(
        core.BENCH_DIR / "layers" / "fleet.kv_used_share.py",
        "fleet.kv_used_share")
    monkeypatch.setattr(jax_engine, "_LAST_RUN", dict(stats))
    assert reader.read(None) == want


def test_tiny_traced_run(tmp_path):
    """3 short and 2 long instances over 300 requests at 100 req/s:
    correct, with every counter and span metric of the cell reported."""
    from bench.run import execute

    root = tiny.make(tmp_path)
    cfg_path = root / "bench" / "configs" / "fleet-azure-10k.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["pools"][0]["instances"], cfg["pools"][1]["instances"] = 3, 2
    cfg["trace_requests"] = 300
    cfg_path.write_text(json.dumps(cfg))
    mix_path = root / "bench" / "traffic" / "single-azure-10k.json"
    mix = json.loads(mix_path.read_text())
    mix.update(requests=300, rate=100.0)
    mix_path.write_text(json.dumps(mix))
    result = execute(CELL, 2**33 + 11, 1.0, True, root=str(root),
                     require_tpu=False)
    assert result["correct"] is True
    assert result["checks"]["records_differing"]["value"] == 0
    metrics = result["metrics"]
    for name in FLEET_METRICS:
        value = metrics[name]["value"]
        assert isinstance(value, float) and math.isfinite(value), name
    assert 0.0 < metrics["fleet.kv_used_share"]["value"] <= 100.0
    assert 0.0 < metrics["fleet.peak_live_share"]["value"] <= 100.0
