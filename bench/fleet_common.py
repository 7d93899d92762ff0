"""What the two fleet drivers share: building the program's inputs from a
configuration file, and checking lanes against the plain reference."""

from __future__ import annotations

import time

import numpy as np

from bench import traffic_gen
from bench.core import Check
from bench.reference import fleet_des


def calibrator(config: dict):
    """A fresh EMA calibrator with the configuration's constants."""
    from repro.core.calibration import EmaCalibrator

    cal = config["sim"]["calibrator"]
    return EmaCalibrator(num_categories=int(cal["categories"]),
                         beta=float(cal["beta"]), gamma=float(cal["gamma"]),
                         c0=float(cal["c0"]))


def program_inputs(config: dict, traffic: dict, seed: int):
    """(columns, TraceColumns, pools, timing, calibrator) for the program."""
    from repro.core.pools import PoolConfig
    from repro.sim.timing import TimingModel
    from repro.traces.generator import TraceColumns

    params = {"requests": int(config["trace_requests"]), **traffic}
    cols = traffic_gen.GENERATORS[traffic["generator"]](params, seed)
    pools = {
        p["name"]: (PoolConfig(p["name"], int(p["c_max"]), int(p["n_seq"]),
                               headroom=float(p["headroom"])),
                    int(p["instances"]))
        for p in config["pools"]
    }
    t = config["timing"]
    timing = TimingModel(t["name"], float(t["w_base"]), float(t["h_per_seq"]),
                         int(t["prefill_chunk"]))
    return cols, TraceColumns(**cols), pools, timing, calibrator(config)


def warm_columns(cols: dict):
    """A trace of the same length whose requests all arrive at once with
    one token in and one out: it drives the same executables as ``cols``
    (they are keyed on the fleet and the trace length, not on the data)
    in a few rounds, so set-up warms them without a whole call."""
    from repro.traces.generator import TraceColumns

    n = len(cols["request_id"])
    one = np.ones(n, np.int64)
    return TraceColumns(**{**cols, "arrival_time": np.zeros(n),
                           "byte_len": 4 * one, "max_output_tokens": one,
                           "true_input_tokens": one,
                           "true_output_tokens": one})


def run_calls(ctx, seconds: float, span: str, call, keep) -> tuple:
    """Whole calls back to back: the first always, each further one while
    it would end inside ``seconds`` at the length of the one before.
    Every call is work of the window. Returns (calls, traced calls,
    elapsed s)."""
    tracer = ctx.tracer
    calls = traced = 0
    last = 0.0
    t0 = time.perf_counter()
    while calls == 0 or time.perf_counter() - t0 + last <= seconds:
        tracer.tick(time.perf_counter() - t0)
        t = time.perf_counter()
        with tracer.span(span):
            out = call()
        last = time.perf_counter() - t
        calls += 1
        traced += tracer.state == "tracing"
        keep(out)
    return calls, traced, time.perf_counter() - t0


def lane_checks(config: dict, cols: dict, lanes: list[tuple]) -> list[Check]:
    """Compare program lanes with the reference.

    ``lanes`` holds ``(thresholds, [records of each call])``; the
    reference runs once per lane and every call's records are compared
    with it. The worst reading of each number over lanes and calls is
    checked against its limit in the configuration's ``checks``."""
    worst = {"route_mismatch": 0, "records_differing": 0, "time_gap_s": 0.0}
    for thresholds, calls in lanes:
        ref, mism = fleet_des.run_reference(
            cols, config, thresholds, program_pool=calls[0]["pool"])
        worst["route_mismatch"] = max(worst["route_mismatch"], mism)
        for rec in calls:
            for k, v in fleet_des.compare_records(rec, ref).items():
                worst[k] = max(worst[k], v)
    limits = config["checks"]
    return [Check(k, float(v), float(limits[k])) for k, v in worst.items()]
