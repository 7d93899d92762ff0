"""One-chip smoke run of the repo's two accelerator paths.

    python chip_smoke.py [--seed N]

1. Prints the device JAX found and stops with a non-zero exit unless it
   is a TPU. There is no way to run this script on the CPU.
2. Turns on the persistent compilation cache
   (:mod:`repro.launch.compile_cache`) and prints its directory.
3. Fleet DES: the 10,000-request Azure-style two-pool fleet of
   ``benchmarks/sim_throughput.py`` runs on the compiled ``jax`` backend
   and on the ``vectorized`` host backend (spillover off on both), and
   must agree under the routed-fleet tolerance of
   ``tests/test_vector_engine.py``. An exact-class single-pool trace is
   compared record for record (reported, not gated), and a 16-lane
   ``run_fleet_grid`` threshold sweep must reproduce the single-lane run
   in its lane at the default threshold.
4. Serving: ``repro.launch.serve.serve`` runs gemma-2b at its published
   widths on a short pool (c_max 2048 x 16 slots) and a long pool
   (8192 x 4) with random weights and greedy sampling. Every request
   must be answered, both pools must serve, the router's calibration
   must move, and the slot-cache decode of one request must give the
   tokens a full ``model.forward`` over the growing sequence gives.
5. Prints ``{"ok": true, "device": {...}}`` as the last line. Any failure
   raises, so that line is printed only when every phase passed.

Times printed along the way are from one smoke run, not benchmark
numbers. Everything runs in this one process, which holds the chip.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.sim_throughput import RATE_PER_10K, build_pools  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.categories import TRUE_BYTES_PER_TOKEN, Category  # noqa: E402
from repro.core.pools import PoolConfig, n_seq_for_cmax  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import serve  # noqa: E402
from repro.serving.kv_cache import bucket_length  # noqa: E402
from repro.sim import A100_LLAMA3_70B, FleetSim, run_fleet_grid  # noqa: E402
from repro.sim import jax_engine  # noqa: E402
from repro.sim.timing import TimingModel  # noqa: E402
from repro.traces import TraceSpec, generate_trace_columns  # noqa: E402

#: Exact-class timing: dyadic constants keep every event time an exact
#: binary float, so the host engines agree bit for bit.
DYADIC = TimingModel(
    "dyadic", w_base=2**-10, h_per_seq=2**-13, prefill_chunk=512
)

#: Default two-pool boundary of ``FleetSim`` (``b_short``).
DEFAULT_THRESHOLD = 8192

_REC_COLS = ("first_token", "finish", "output_tokens", "preemptions",
             "truncated", "rejected")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _close(a: float, b: float, rel: float) -> bool:
    return abs(a - b) <= rel * abs(b)


def _record_table(sim: FleetSim) -> dict[str, np.ndarray]:
    """Every pool's records as columns, sorted by request id."""
    parts = [p.record_arrays() for p in sim.pools.values()]
    ids = np.concatenate([a["request_id"] for a in parts])
    order = np.argsort(ids, kind="stable")
    table = {"request_id": ids[order]}
    for c in _REC_COLS:
        table[c] = np.concatenate([a[c] for a in parts])[order]
    return table


def _table_diff(a: dict, b: dict) -> tuple[int, float]:
    """(records that differ, largest |difference| of a time column)."""
    require(np.array_equal(a["request_id"], b["request_id"]),
            "record sets differ")
    differ = np.zeros(len(a["request_id"]), bool)
    worst = 0.0
    for c in _REC_COLS:
        x, y = a[c], b[c]
        if x.dtype.kind == "f":
            same = (x == y) | (np.isnan(x) & np.isnan(y))
            d = np.abs(x - y)
            d = d[np.isfinite(d)]
            worst = max(worst, float(d.max()) if d.size else 0.0)
        else:
            same = x == y
        differ |= ~same
    return int(differ.sum()), worst


def check_routed(jx, vec, n: int) -> None:
    """The routed-fleet tolerance of ``tests/test_vector_engine.py``."""
    sj, sv = jx.summary, vec.summary
    require(sj.num_requests == sv.num_requests, "request counts differ")
    require(_close(sj.completed, sv.completed, 0.01),
            f"completed {sj.completed} vs {sv.completed}")
    require(_close(sj.ttft_p99, sv.ttft_p99, 0.15),
            f"ttft_p99 {sj.ttft_p99} vs {sv.ttft_p99}")
    require(_close(sj.tpot_p99, sv.tpot_p99, 0.15),
            f"tpot_p99 {sj.tpot_p99} vs {sv.tpot_p99}")
    for name, frac in vec.router_stats["fractions"].items():
        got = jx.router_stats["fractions"][name]
        require(abs(got - frac) <= 0.02, f"{name} fraction {got} vs {frac}")
    for res in (jx, vec):
        require(sum(res.router_stats["routed"].values()) == n,
                "a request was not routed")


def exact_class(requests: int, seed: int) -> dict:
    """Single pool, dyadic timing, ``coalesce_dt=0``: jax vs vectorized."""
    cols = generate_trace_columns(
        TraceSpec(trace="azure", num_requests=requests,
                  rate=requests / 50.0, seed=seed)
    )
    cfg = PoolConfig("p", 8192, n_seq_for_cmax(8192))
    tables = {}
    for backend in ("vectorized", "jax"):
        sim = FleetSim({"p": (cfg, 2)}, DYADIC, backend=backend,
                       coalesce_dt=0.0)
        sim.run(cols)
        tables[backend] = _record_table(sim)
    n_diff, worst = _table_diff(tables["jax"], tables["vectorized"])
    log(f"exact class n={requests}: bit_identical={n_diff == 0} "
        f"records_differing={n_diff} max_abs_time_diff={worst!r}")
    return {"bit_identical": n_diff == 0, "records_differing": n_diff,
            "max_abs_time_diff": worst}


def fleet_phase(*, requests: int = 10_000, lanes: int = 16,
                exact_requests: int = 2_000, seed: int = 42) -> dict:
    """Compiled fleet DES on the device, checked against the host engine."""
    rate = max(50.0, RATE_PER_10K * requests / 10_000)
    cols = generate_trace_columns(
        TraceSpec(trace="azure", num_requests=requests, rate=rate, seed=seed)
    )
    pools, _ = build_pools(cols, rate, 2)
    timing = A100_LLAMA3_70B

    def fleet(backend: str) -> FleetSim:
        return FleetSim(pools, timing, backend=backend, spillover=False)

    comp = jax_engine.aot_compile(fleet("jax"), cols)
    sim_j = fleet("jax")
    t0 = time.perf_counter()
    res_j = sim_j.run(cols)  # results come back as host arrays
    wall_j = time.perf_counter() - t0
    run_stats = jax_engine.last_run_stats()
    log(f"fleet jax n={requests}: lower_s={comp['lower_s']!r} "
        f"compile_s={comp['compile_s']!r} wall_s={wall_j!r} "
        f"stats={run_stats} (one smoke run, not a benchmark)")

    t0 = time.perf_counter()
    res_v = fleet("vectorized").run(cols)
    wall_v = time.perf_counter() - t0
    log(f"fleet vectorized n={requests}: wall_s={wall_v!r} "
        f"(one smoke run, not a benchmark)")
    check_routed(res_j, res_v, requests)
    log(f"routed jax vs vectorized within tolerance: completed "
        f"{res_j.summary.completed}/{res_v.summary.completed} ttft_p99 "
        f"{res_j.summary.ttft_p99!r}/{res_v.summary.ttft_p99!r}")

    exact = exact_class(exact_requests, seed + 1)

    grid_th = sorted({int(t) for t in np.linspace(512, DEFAULT_THRESHOLD,
                                                  lanes)})
    require(len(grid_th) == lanes and grid_th[-1] == DEFAULT_THRESHOLD,
            "grid thresholds")
    t0 = time.perf_counter()
    grid = run_fleet_grid(cols, pools, timing,
                          thresholds=[[t] for t in grid_th],
                          return_records=True)
    wall_g = time.perf_counter() - t0
    grid_stats = jax_engine.last_run_stats()
    grid_comp = [s for s in jax_engine.compile_stats() if s["grid"]][-1]
    log(f"grid g={lanes} n={requests}: compile_s={grid_comp['compile_s']!r} "
        f"wall_s_incl_compile={wall_g!r} stats={grid_stats} "
        f"(one smoke run, not a benchmark)")

    k = grid_th.index(DEFAULT_THRESHOLD)
    single = _record_table(sim_j)
    order = np.argsort(np.asarray(cols.arrival_time), kind="stable")
    ids = np.asarray(cols.request_id, np.int64)[order]
    pos = np.searchsorted(single["request_id"], ids)
    rec = grid.records
    lane = {
        "first_token": rec["first"][k], "finish": rec["finish"][k],
        "output_tokens": rec["out"][k], "preemptions": rec["pre"][k],
        "truncated": rec["trunc"][k], "rejected": rec["rej"][k],
    }
    for c in _REC_COLS:
        require(np.array_equal(np.asarray(lane[c]), single[c][pos],
                               equal_nan=single[c].dtype.kind == "f"),
                f"grid lane {k} column {c} differs from the single-lane run")
    require(int(grid.routed[k, 0]) == res_j.router_stats["routed"]["short"],
            "grid lane routed a different short count")
    log(f"grid lane {k} (threshold {DEFAULT_THRESHOLD}) equals the "
        f"single-lane run")
    return {"compile": comp, "wall_jax_s": wall_j, "wall_vectorized_s": wall_v,
            "run_stats": run_stats, "exact": exact, "grid_wall_s": wall_g,
            "grid_stats": grid_stats}


def serve_workload(vocab: int, short_cmax: int, n_short: int, n_long: int,
                   seed: int) -> list:
    """Requests that the router must split across both pools.

    Prompt bytes follow the categories' true bytes/token. The short
    requests' prompts share one prefill bucket, and so do the long ones'.
    A long request's prompt plus output exceeds the short pool's budget
    under any calibration state the categories used here can reach.
    """
    rng = np.random.default_rng(seed)
    cats = (Category.ENGLISH_PROSE, Category.MIXED_OTHER)
    work = []
    for i in range(n_short + n_long):
        is_long = i >= n_short
        if is_long:
            n = short_cmax - short_cmax // 32 - i % 4
            mx = short_cmax // 8
        else:
            n = max(4, short_cmax // 16 - i % 4)
            mx = 8
        cat = cats[i % 2]
        toks = [int(t) for t in rng.integers(0, vocab, n)]
        work.append((toks, round(n * TRUE_BYTES_PER_TOKEN[cat]), mx, int(cat)))
    # long requests first, so their decodes overlap the short ones
    return work[n_short:] + work[:n_short]


def decode_matches_forward(model, params, prompt, served) -> dict:
    """Greedy tokens from a full forward over prompt + served[:-1].

    The forward is causal, so one right-padded pass gives the logits of
    every position. A token that differs from the served one is
    accepted only as a bf16 near-tie: its forward logit within 8 ulps of
    the row maximum. This sees a wrong KV write only where it changes
    the greedy tokens; at random weights they may barely depend on the
    context."""
    seq = list(prompt) + list(served[:-1])
    length = len(seq)
    toks = np.zeros((1, bucket_length(length, multiple=64)), np.int32)
    toks[0, :length] = seq
    logits, _ = jax.jit(model.forward)(params, {"tokens": jnp.asarray(toks)})
    rows = np.asarray(
        logits[0, len(prompt) - 1:length].astype(jnp.float32)
    )
    served = np.asarray(served)
    fwd = rows.argmax(axis=-1)
    idx = np.arange(len(served))
    gap = rows[idx, fwd] - rows[idx, served]
    top = np.abs(rows[idx, fwd])
    ulp = np.exp2(np.floor(np.log2(np.maximum(top, 2.0**-8))) - 7)
    exact = fwd == served
    near = ~exact & (gap <= 8 * ulp)
    require(bool((exact | near).all()),
            f"decode tokens {served.tolist()} vs forward {fwd.tolist()} "
            f"(logit gaps {gap.tolist()})")
    return {"served": served.tolist(), "forward": fwd.tolist(),
            "exact": int(exact.sum()), "near_ties": int(near.sum()),
            "gaps": gap.tolist()}


def serve_phase(*, arch: str = "gemma-2b", reduced: bool = False,
                short: tuple[int, int] = (2048, 16),
                long: tuple[int, int] = (8192, 4),
                n_short: int = 12, n_long: int = 4, check_steps: int = 4,
                seed: int = 0) -> dict:
    """Two-pool serving through ``serve()``, checked against ``forward``."""
    cfg = get_config(arch)
    vocab = cfg.reduced().vocab if reduced else cfg.vocab
    work = serve_workload(vocab, short[0], n_short, n_long, seed)
    t0 = time.perf_counter()
    out = serve(arch, short_cmax=short[0], short_slots=short[1],
                long_cmax=long[0], long_slots=long[1], seed=seed,
                temperature=0.0, reduced=reduced, workload=work)
    wall = time.perf_counter() - t0
    responses = out["responses"]
    require(sorted(r.request_id for r in responses) == list(range(len(work))),
            "not every request got a response")
    by_pool = collections.Counter(r.pool for r in responses)
    require(by_pool["short"] >= 1 and by_pool["long"] >= 1,
            f"both pools must serve: {dict(by_pool)}")
    counts = out["stats"]["router"]["calibration"]["count"]
    require(sum(counts) > 0, "router calibration counts did not move")
    log(f"serve {arch} reduced={reduced}: {len(responses)} responses "
        f"{dict(by_pool)} calibration counts {counts} wall_s_incl_compile="
        f"{wall!r} (one smoke run, not a benchmark)")

    r = next(r for r in responses
             if r.pool == "short" and len(r.output_tokens) >= check_steps)
    check = decode_matches_forward(out["model"], out["params"],
                                   work[r.request_id][0],
                                   r.output_tokens[:check_steps])
    log(f"decode vs forward, request {r.request_id}: {check}")
    return {"by_pool": dict(by_pool), "calibration_counts": counts,
            "wall_s": wall, "forward_check": check}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    platform, kind, count = dev.platform, dev.device_kind, len(jax.devices())
    log(f"device platform={platform} kind={kind} count={count}")
    if platform != "tpu":
        print(f"[smoke] JAX found platform {platform!r}, not a TPU; "
              f"nothing was run", file=sys.stderr, flush=True)
        return 1
    log(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    fleet_phase(seed=42 + args.seed)
    log(f"fleet phase done in {time.perf_counter() - t0!r} s")
    t0 = time.perf_counter()
    serve_phase(seed=args.seed)
    log(f"serve phase done in {time.perf_counter() - t0!r} s")

    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
