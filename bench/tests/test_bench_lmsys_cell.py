"""The LMSYS fleet cell: found by name, sized by the program's own plan,
its reader of ``fleet.peak_live_share``, and a whole traced run of a tiny
copy on the CPU."""

from __future__ import annotations

import json
import math

import pytest

from bench import core
from bench.tests import tiny

CELL = "fleet-lmsys-1k.single"
FLEET_METRICS = ("fleet.rounds_per_req", "fleet.adm_waves_per_round",
                 "fleet.evict_pass_share", "fleet.live_slot_share",
                 "fleet.host_ms_per_call", "fleet.rec_trips_per_round",
                 "fleet.peak_live_share")
# The device trace of one whole call of this cell stops partway (the
# profiler drops events), so the metrics read from it would read part of
# the call: the cell does not list them.
TRUNCATED_TRACE_METRICS = ("fleet.device_idle_share",
                           "fleet.device_us_per_round")


def _config():
    return json.loads(
        (core.BENCH_DIR / "configs" / "fleet-lmsys-1k.json").read_text())


def test_cell_resolves_with_its_fleet_metrics():
    bench = core.load_benchmark()
    cell = core.resolve(bench, CELL)
    assert cell.chips == 1
    assert cell.traffic["driver"] == "fleet_single"
    assert cell.traffic["trace"] == "lmsys"
    assert cell.traffic["requests"] == cell.config["trace_requests"]
    assert {m["name"] for m in cell.end_to_end} == {"sim_lane_req_per_s",
                                                    "setup_s"}
    assert sorted(cell.readers) == sorted(FLEET_METRICS)
    for m in bench["per_layer"]:
        if m["name"] in TRUNCATED_TRACE_METRICS:
            assert CELL not in m["workloads"], m["name"]
    entry = next(c for c in bench["configs"] if c["name"] == "fleet-lmsys-1k")
    assert set(entry["reduced"]) == set(cell.config["reduced"])
    azure = next(c for c in bench["configs"] if c["name"] == "fleet-azure-1k")
    assert entry["source"] != azure["source"]


def test_pools_are_the_programs_plan():
    """150 short and 6 long instances: ``plan_fleet`` for LMSYS at
    1,000 req/s over 20,000 requests drawn at seed 42."""
    from repro.sim import A100_LLAMA3_70B, plan_fleet
    from repro.traces import TraceSpec, generate_trace

    cfg = _config()
    reqs = generate_trace(TraceSpec(trace="lmsys", num_requests=20_000,
                                    rate=1000.0, seed=42))
    plan = plan_fleet("lmsys", reqs, A100_LLAMA3_70B, 1000.0)
    short, long_ = cfg["pools"]
    assert (short["instances"], long_["instances"]) == (
        plan.short.instances, plan.long.instances)
    assert (short["n_seq"], long_["n_seq"]) == (128, 16)
    t = cfg["timing"]
    assert (t["w_base"], t["h_per_seq"], t["prefill_chunk"]) == (
        A100_LLAMA3_70B.w_base, A100_LLAMA3_70B.h_per_seq,
        A100_LLAMA3_70B.prefill_chunk)


@pytest.mark.parametrize("stats, want", [
    ({"live_peak": 4825, "real_slot_rows": 19_296}, 100.0 * 4825 / 19_296),
    ({"live_peak": 0, "real_slot_rows": 400}, 0.0),
    ({"rounds": 8, "live_slot_rounds": 40}, None),  # without the counter
    ({}, None),  # no run yet
])
def test_reader(monkeypatch, stats, want):
    from repro.sim import jax_engine

    reader = core.load_module(
        core.BENCH_DIR / "layers" / "fleet.peak_live_share.py",
        "fleet.peak_live_share")
    monkeypatch.setattr(jax_engine, "_LAST_RUN", dict(stats))
    assert reader.read(None) == want


def test_tiny_traced_run(tmp_path):
    """3 short and 1 long instances over 300 requests at 30 req/s: correct,
    with every counter and span metric of the cell reported."""
    from bench.run import execute

    root = tiny.make(tmp_path)
    cfg_path = root / "bench" / "configs" / "fleet-lmsys-1k.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["pools"][0]["instances"], cfg["pools"][1]["instances"] = 3, 1
    cfg["trace_requests"] = 300
    cfg_path.write_text(json.dumps(cfg))
    mix_path = root / "bench" / "traffic" / "single-lmsys.json"
    mix = json.loads(mix_path.read_text())
    mix.update(requests=300, rate=30.0)
    mix_path.write_text(json.dumps(mix))
    result = execute(CELL, 2**33 + 7, 1.0, True, root=str(root),
                     require_tpu=False)
    assert result["correct"] is True
    assert result["checks"]["records_differing"]["value"] == 0
    metrics = result["metrics"]
    for name in ("fleet.rounds_per_req", "fleet.adm_waves_per_round",
                 "fleet.evict_pass_share", "fleet.live_slot_share",
                 "fleet.host_ms_per_call", "fleet.rec_trips_per_round",
                 "fleet.peak_live_share"):
        value = metrics[name]["value"]
        assert isinstance(value, float) and math.isfinite(value), name
    assert 0.0 < metrics["fleet.peak_live_share"]["value"] <= 100.0
