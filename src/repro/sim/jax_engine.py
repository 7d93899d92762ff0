"""JIT-compiled fleet backend (``backend="jax"``) + vmapped sensitivity grids.

The third simulator tier. The reference engine (:mod:`repro.sim.engine`)
is one Python object per sequence; the vectorized engine
(:mod:`repro.sim.vector_engine`) is masked NumPy over ``(instances,
n_seq)`` arrays with a Python event loop. This module compiles the *whole*
event loop — admission, decode k-jumps, completion, truncation, AND the
order-free batch preemption pass — into one ``lax.while_loop`` body, so an
entire fleet run is a single XLA executable with no host round-trips. That
buys the thing neither host tier can do: ``jax.vmap`` over the loop turns a
16–256-point sensitivity sweep (thresholds × fleet sizes × controller
gains) into one batched device program (:func:`run_fleet_grid`).

Simulation semantics
--------------------
Identical to the host backends at ``coalesce_dt=0`` (per-arrival sync):

* fixed-shape per-pool slot state ``(I, S)`` carried through the loop;
* head-of-line FIFO admission with KV-block reservation, as an inner
  fixpoint ``while_loop`` (one admission wave per iteration — instances
  are independent, so wave order equals the host's per-instance order);
* event-distance k-jumps with the same integer/float formulas and the
  same IEEE-754 op order as ``VectorPoolSim._round`` (times are float64
  — the entry points run under ``jax.enable_x64``);
* the shared order-free batch preemption rule (advance → truncate →
  completion credit → evict the minimal youngest-first prefix of decoding
  survivors → allocate growth) as a *sort-free* victim-selection pass:
  pairwise-comparison ranks and masked prefix sums over the tiny
  ``(S, S)`` slot square replace the host tier's ``lexsort`` + ``cumsum``
  (XLA:CPU sorts, batched gathers, and batched scatters all lower to
  ~40–50 µs serial loops inside a while body; the one-hot reduces fuse).
  The selected victims are identical, so routerless single-pool runs are
  *bit-identical* to both host backends on the CPU (asserted by
  ``tests/test_vector_engine.py``). On a TPU, where XLA emulates
  float64, event times can differ from the host's in their last bits.

FIFO queues are request-indexed linked lists (``q_next[rid]`` + per
instance head/tail); preempted sequences go to a bounded per-instance
victim stash that the admission loop drains before the FIFO (capacity
``n_seq`` suffices: FIFO admits only while the stash is empty, so
``n_active + stash ≤ n_seq`` is invariant).

Carry layout and donation contract
----------------------------------
The run is three nested ``lax.while_loop``\\ s with deliberately *small*
carries — under ``vmap`` every loop iteration pays a masked select over
its whole carry, so what rides each carry is the backend's main cost
model (``benchmarks/sim_throughput.py`` tracks the byte totals as
``carry_bytes`` / ``sweep_carry_bytes`` / ``drain_carry_bytes``):

* **outer epoch loop** — one iteration per arrival burst: drain all
  arrivals that precede the next instance wake, then sweep rounds until
  the next arrival. Iteration count is surfaced as ``iters`` (bounded by
  ``n + 1``: every non-final epoch dispatches at least one arrival).
* **arrival drain** — carries only dispatch state: the FIFO linked
  lists, per-instance ``load``/``wake``, controller/window state, and
  the single ``(n+1,)`` pool-assignment record. No ``(I, S)`` slot
  arrays, no other record columns.
* **round sweep** — carries the slot arrays plus exactly the record
  columns that completion scatters write (``first``/``finish``/``out``/
  ``pre``/``trunc``) and the admission-reject staging column ``rejt``,
  plus the loop counters (``ctr``: a few scalars). Iteration count is
  surfaced as ``rounds`` (the pre-coalescing outer loop ran one round
  per outer iteration, so ``rounds / iters`` is the measured coalescing
  factor).

Per-request record arrays live in **preallocated donated buffers**: the
compiled entry takes a third argument ``rec0`` (see ``_fresh_records``)
that is donated to XLA (``jax.jit(..., donate_argnums=(2,))``), so the
in-loop scatters update the caller's buffers in place instead of copying
the record tree through every call. Callers must therefore pass *fresh*
buffers on every call and never reuse a previously-donated array — both
entry points allocate via ``_fresh_records`` per call, which the
donated-buffer parity tests pin down. Submit-time rejection is a pure
function of the recorded pool id and the trace, and admission-time
rejection is staged as a reject *timestamp* (``rejt``, +inf = not
rejected), so the boolean ``rej`` column and the reject first/finish
times are folded in once after the loop rather than scattered inside it.

The executables themselves are compiled ahead of time and cached
(:func:`aot_compile` / ``_aot``): ``.lower().compile()`` under
``enable_x64`` keyed by the static ``(spec, n, grid, g)`` shape, with
wall-clock lower/compile times recorded in ``_COMPILE_STATS`` so the
benchmark's ``jax_compile`` row measures compilation alone. The hot
decode-advance pass is :func:`repro.kernels.sim_decode.decode_advance_jnp`,
the same pass on every backend.

Instrumentation
---------------
Always on. Inside the executable, each round phase runs under a
``jax.named_scope`` (``_make_core``), and the carry counts admission
waves, eviction passes, eviction need and live slot rows as the device
executed them. On the host, each entry call is the span
``repro.sim.run`` with children for routing precompute, compilation,
staging, the device run and result assembly: ``TraceAnnotation`` events
on a device profile's clock, whose seconds also land in
:func:`last_run_stats` (one timing for both).

Routing, calibration, and control
---------------------------------
* **Routing** is fused into the dispatch branch as a ``searchsorted``
  against the *carried* threshold vector (shared helper
  :func:`repro.core.router.jax_pool_ids` — the same decision the batch
  routing kernel makes) — honest under threshold / controller vmap axes.
  Per-request budgets are precomputed on the host by folding the
  byte-length observation stream through the cached EMA kernels
  (:func:`precompute_budget_trajectory`) in arrival order with the same
  ramped epoch schedule the vectorized backend uses. Approximations vs
  the host routed path (documented, tolerance-class): feedback folds
  arrival-ordered trace observations instead of completion-ordered ones,
  and load-dependent spillover is off (static N-way + hard-constraint
  clamp only).
* **Adaptive control** mirrors :class:`repro.core.adaptive.AdaptiveController`
  in-step: the same AIMD decision rule, constants, and strict-ordering
  clamp run inside the compiled dispatch branch on the same
  dispatched-request windows, so controller *gains* can be a vmap axis.
* **Telemetry** is collected as per-window device snapshots (queue depth,
  active, KV-free, cumulative error counters, thresholds) and replayed
  into the host :class:`repro.obs.timeseries.FleetTelemetry` after the
  run — same windows, same columns; per-window calibration-error series
  use the final EMA state (device runs don't carry the float EMA).

When to prefer which tier: ``reference`` for unit-level ground truth;
``vectorized`` for one-off large host runs with faults / spillover /
event tracing; ``jax`` for grid sweeps and controller tuning where
compile time amortizes over many lanes. Fault injection is not supported
on this backend (``FleetSim`` raises).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import time
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.adaptive import (
    BoundaryMove,
    DEFAULT_DECREASE_FACTOR,
    DEFAULT_ERROR_RATE_HI,
    DEFAULT_INCREASE_STEP,
    DEFAULT_OVERLOAD_RATIO_HI,
)
from repro.core.calibration import (
    EmaCalibrator,
    _count_trace,
    _estimate_budget_kernel,
    _update_stream_kernel,
)
from repro.core.pools import KV_BLOCK_TOKENS, PoolConfig, TOTAL_KV_BLOCKS
from repro.core.router import jax_pool_ids
from repro.kernels.sim_decode import decode_advance_jnp
from repro.sim.engine import _blocks_for
from repro.sim.timing import TimingModel
from repro.traces.generator import TraceColumns

#: Sentinels for "no constraint" in masked min-reductions (int32-safe).
_BIG_I = 1 << 30
_BIG_F = 1.0e18

#: Donated record buffers (name, dtype, width). Same-dtype columns are
#: packed along a trailing width axis so each completion round issues
#: one scatter per buffer instead of one per column — XLA:CPU charges
#: ~40 µs per batched scatter inside a while body regardless of row
#: width. ``recf`` packs [first_token, finish]; ``reci`` packs
#: [out_tokens, preemptions, truncated(0/1)]. ``rejt`` stages the
#: admission-reject timestamp (+inf = not rejected); the boolean ``rej``
#: column is derived post-loop, so it never rides a loop carry.
_REC_DTYPES = (
    ("recf", np.float64, 2),
    ("reci", np.int32, 3),
    ("pool", np.int32, 1),
    ("rejt", np.float64, 1),
)

#: Per-pool state that the arrival drain actually mutates (FIFO lists,
#: load-balance picks, wake seeding, submit-reject counter). Everything
#: else is loop-invariant during a drain and stays out of its carry.
_DRAIN_POOL_KEYS = ("qnext", "qh", "qt", "qlen", "load", "wake", "nrej")

# ---------------------------------------------------------------------------
# Static compile-time description
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _PoolSpec:
    """Static shape/capacity facts for one pool (hashable → jit cache key)."""

    name: str
    c_max: int
    n_seq: int
    total_blocks: int
    max_inst: int  # array dimension I (≥ every lane's instance count)


@dataclasses.dataclass(frozen=True)
class _SimSpec:
    pools: tuple[_PoolSpec, ...]
    w: float  # roofline W (seconds)
    h: float  # roofline H (seconds)
    prefill_chunk: int
    win_size: int  # monitoring window in dispatched requests; 0 = off


def _pool_spec(name: str, cfg: PoolConfig, max_inst: int) -> _PoolSpec:
    total = min(TOTAL_KV_BLOCKS, cfg.n_seq * _blocks_for(cfg.c_max))
    return _PoolSpec(
        name=name,
        c_max=int(cfg.c_max),
        n_seq=int(cfg.n_seq),
        total_blocks=int(total),
        max_inst=int(max_inst),
    )


# ---------------------------------------------------------------------------
# Carry construction (shared by the compiled core and the size probe)
# ---------------------------------------------------------------------------


def _init_pools(spec: _SimSpec, n: int) -> dict:
    """Stacked ``(P, I, S)`` pool state — one pytree for every pool.

    Pools are padded to the widest instance/slot counts so a single
    traced round body covers all of them (the XLA:CPU backend is
    op-dispatch bound, so P separately-traced pool bodies cost ~P× one
    stacked body). Padding is inert by construction: padded slots are
    unoccupied and guarded by the per-pool ``n_seq`` admission cap,
    padded instances never wake (``wake = inf``) and contribute zero
    free blocks to the telemetry sums.
    """
    i32 = jnp.int32
    f64 = jnp.float64
    P = len(spec.pools)
    I = max(ps.max_inst for ps in spec.pools)
    S = max(ps.n_seq for ps in spec.pools)
    ivalid = np.arange(I)[None, :] < np.asarray(
        [ps.max_inst for ps in spec.pools]
    )[:, None]
    tblocks = np.asarray([ps.total_blocks for ps in spec.pools], np.int32)
    z2 = jnp.zeros((P, I, S), i32)
    return {
        "occ": jnp.zeros((P, I, S), bool),
        "rid": jnp.full((P, I, S), -1, i32),
        "enq": jnp.zeros((P, I, S), f64),
        "inp": z2,
        "outp": z2,
        "pre": z2,
        "rem": z2,
        "gen": z2,
        "blk": z2,
        "ft": jnp.full((P, I, S), jnp.nan, f64),
        "tr": jnp.zeros((P, I, S), bool),
        "pc": z2,
        "sq": z2,
        "free": jnp.asarray(
            np.where(ivalid, tblocks[:, None], 0), i32
        ),
        "wake": jnp.full((P, I), jnp.inf, f64),
        "nact": jnp.zeros((P, I), i32),
        "qlen": jnp.zeros((P, I), i32),
        "load": jnp.zeros((P, I), i32),
        "qh": jnp.full((P, I), -1, i32),
        "qt": jnp.full((P, I), -1, i32),
        "qnext": jnp.full((P, n + 1), -1, i32),
        "vrid": jnp.zeros((P, I, S), i32),
        "vinp": jnp.zeros((P, I, S), i32),
        "vpc": jnp.zeros((P, I, S), i32),
        "vcnt": jnp.zeros((P, I), i32),
        "sqc": jnp.zeros((P,), i32),
        "npre": jnp.zeros((P,), i32),
        "nrej": jnp.zeros((P,), i32),
        "ntr": jnp.zeros((P,), i32),
    }


def _init_windows(P: int, nb: int, win_cap: int) -> dict:
    i32 = jnp.int32
    f64 = jnp.float64
    return {
        "t_req": jnp.zeros((win_cap,), i32),
        "now": jnp.zeros((win_cap,), f64),
        "th": jnp.zeros((win_cap, nb), i32),
        "queue": jnp.zeros((win_cap, P), i32),
        "active": jnp.zeros((win_cap, P), i32),
        "freeb": jnp.zeros((win_cap, P), i32),
        "pre": jnp.zeros((win_cap, P), i32),
        "rej": jnp.zeros((win_cap, P), i32),
        "trunc": jnp.zeros((win_cap, P), i32),
    }


def _init_counters() -> dict:
    """Loop counters carried through the rounds (see ``last_run_stats``).

    ``live_slot_rounds`` is int64: live slots summed over rounds passes
    2**31 on hour-long traces of a full fleet."""
    z = jnp.asarray(0, jnp.int32)
    return {
        "rounds": z,
        "adm_waves": z,
        "evict_runs": z,
        "evict_need": z,
        "live_slot_rounds": jnp.asarray(0, jnp.int64),
    }


def _fresh_records(n: int, g: Optional[int] = None) -> dict:
    """Freshly-zeroed donated record buffers for one compiled call.

    Donation contract: these arrays are consumed by the executable —
    allocate a new set per call, never hand back a previously-donated
    buffer. ``rejt`` is +inf-filled (no admission reject)."""
    base = (n + 1,) if g is None else (g, n + 1)
    buf = {}
    for name, dt, w in _REC_DTYPES:
        shape = base if w == 1 else base + (w,)
        buf[name] = (
            np.full(shape, np.inf, dt)
            if name == "rejt"
            else np.zeros(shape, dt)
        )
    return buf


def _unpack_records(rec: dict, n: int) -> dict:
    """Split the packed record buffers back into named host columns.

    Handles single-lane ``(n + 1, …)`` and grid ``(g, n + 1, …)``
    shapes alike (the request axis is always the one sliced by ``:n``,
    dropping the scratch row)."""
    rf = rec["recf"][..., :n, :]
    ri = rec["reci"][..., :n, :]
    return {
        "first": rf[..., 0],
        "finish": rf[..., 1],
        "out": ri[..., 0],
        "pre": ri[..., 1],
        "trunc": ri[..., 2].astype(bool),
        "pool": rec["pool"][..., :n],
        "rejt": rec["rejt"][..., :n],
        "rej": rec["rej"][..., :n],
    }


# ---------------------------------------------------------------------------
# The compiled core
# ---------------------------------------------------------------------------


def _make_core(
    spec: _SimSpec,
    n: int,
    return_records: bool,
    lanes: Optional[str] = None,
):
    """Build the single-lane simulation function for one (spec, n).

    Returned function signature: ``core(trace, lane, rec0) -> dict``
    where ``trace`` holds shared arrival-ordered arrays, ``lane`` the
    per-lane (vmappable) parameters, and ``rec0`` the donated record
    buffers (see ``_fresh_records``). Must be traced/executed inside an
    ``enable_x64()`` context — event times are float64 accumulations.

    The pool state is a single stacked ``(P, I, S)`` pytree (see
    ``_init_pools``) so one traced round body covers every pool — on the
    op-dispatch-bound XLA:CPU backend P separately-traced bodies cost
    ~P× as much.

    ``lanes`` is the ``vmap`` axis name in grid mode and ``None`` for a
    single lane. A single lane short-circuits the eviction pass with
    ``lax.cond`` (the gate) when no instance is over budget; the skipped
    branch is bit-identical to the masked pass (``jsel = 0`` evicts
    nothing), so gating never changes results — but under ``vmap`` a
    batched ``cond`` runs both branches anyway, so grids run the pass
    ungated. The mode also selects the outer-loop shape: nested
    drain→sweep epochs for the single-lane path, drain + exactly one
    round per outer iteration for vmapped grids (a nested sweep loop
    would run to the max round count over lanes per epoch — a measured
    5.6× lockstep blowup at G=16).

    Each round phase runs under a ``jax.named_scope`` (``dispatch`` with
    ``window_step`` inside it, ``admit``, ``advance``,
    ``record_scatter``, ``evict``, ``round_update``; ``fold`` after the
    loop), so every HLO op carries its phase in its metadata and a
    device profile's per-op events group by phase. The loop counters
    (``ctr`` in the carry, returned in ``out``) count what the device
    executed: under ``vmap`` a batched ``while`` runs until its last
    lane is done, so the admission trip count and the eviction need are
    ``lax.pmax``-ed over ``lanes`` (the identity for a single lane).
    """
    P = len(spec.pools)
    win = spec.win_size
    win_cap = (n // win + 2) if win > 0 else 1
    nb = max(P - 1, 1)  # threshold-column width (≥1 keeps shapes non-empty)
    i32 = jnp.int32
    f64 = jnp.float64
    W = np.float64(spec.w)
    H = np.float64(spec.h)
    CHUNK = spec.prefill_chunk
    gate = lanes is None
    I = max(ps.max_inst for ps in spec.pools)
    S = max(ps.n_seq for ps in spec.pools)
    # Per-pool parameters as (P,) closure constants over the stacked
    # state (dtype-pinned so padding arithmetic stays int32).
    cmax_v = jnp.asarray([ps.c_max for ps in spec.pools], jnp.int32)
    nseq_v = jnp.asarray([ps.n_seq for ps in spec.pools], jnp.int32)
    tblk_v = jnp.asarray(
        [ps.total_blocks for ps in spec.pools], jnp.int32
    )
    pg2 = jnp.arange(P)[:, None]
    ig2 = jnp.arange(I)[None, :]

    _advance_1 = functools.partial(decode_advance_jnp, w=W, h=H, chunk=CHUNK)

    def advance_all(t_limit, *args):
        # One vmapped pass over the pool axis; c_max rides along as a
        # traced per-pool scalar (pure arithmetic in the pass).
        return jax.vmap(
            lambda cm, *a: _advance_1(t_limit, *a, c_max=cm),
            in_axes=(0,) * (len(args) + 1),
        )(cmax_v, *args)

    def blocks_for(tok):
        return jnp.maximum(1, (tok + (KV_BLOCK_TOKENS - 1)) // KV_BLOCK_TOKENS)

    def wake_min_all(pools_):
        return jnp.min(pools_["wake"])

    def executed(x):
        # What the device ran: a batched ``while`` runs until its last
        # lane is done, so under vmap that is the lane maximum.
        return x if lanes is None else lax.pmax(x, lanes)

    def core(trace, lane, rec0):
        _count_trace(("sim_core", P, n, bool(return_records)))
        arr_t = trace["arr"]
        inp_t = trace["inp"]
        out_t = trace["outp"]
        bud_t = trace["budget"]
        ctrl = lane["ctrl"]

        def next_arr_at(a):
            return jnp.where(a < n, arr_t[jnp.minimum(a, n - 1)], jnp.inf)

        # ---- arrival drain (small carry: dispatch state only) -------------
        @jax.named_scope("dispatch")
        def drain(c):
            # Loop-invariant pool state during a drain: dispatch touches
            # only the FIFO/load/wake/nrej fields, so the window snapshot's
            # other inputs are frozen closures — values identical to the
            # full-carry formulation, but the masked per-iteration select
            # covers only the small carry below.
            frozen = {
                "npre": c["pools"]["npre"],
                "ntr": c["pools"]["ntr"],
                "nact": c["pools"]["nact"],
                "free": c["pools"]["free"],
            }

            # ---- monitoring window + in-step AIMD controller --------------
            @jax.named_scope("window_step")
            def window_step(sc, now_t):
                fire = (sc["win_seen"] - sc["win_prev"]) >= win
                cur = frozen["npre"] + sc["pools"]["nrej"] + frozen["ntr"]
                delta = cur - sc["prev_err"]
                wr = sc["win_seen"] - sc["win_prev"]
                queues = jnp.sum(sc["pools"]["qlen"], axis=1, dtype=i32)
                pressure = queues.astype(jnp.float32) / jnp.maximum(
                    1, lane["ninst"]
                ).astype(jnp.float32)
                old = sc["th"]
                moved = jnp.asarray(False)
                th = old
                if P > 1:
                    # AIMD per boundary — the exact decision rule and
                    # constants of AdaptiveController._aimd_move / update().
                    wrf = jnp.maximum(wr, 1).astype(jnp.float32)
                    props = []
                    for k in range(P - 1):
                        err_rate = delta[k].astype(jnp.float32) / wrf
                        p_lo, p_hi = pressure[k], pressure[k + 1]
                        dec = (err_rate > ctrl["err_hi"]) | (
                            (p_lo > ctrl["over_hi"] * jnp.maximum(p_hi, 0.25))
                            & (p_lo > 1.0)
                        )
                        inc = (~dec) & (p_hi < 0.25) & (p_lo < 1.0)
                        down = (
                            old[k].astype(jnp.float32) * ctrl["factor"]
                        ).astype(i32)
                        props.append(
                            jnp.where(
                                dec,
                                down,
                                jnp.where(inc, old[k] + ctrl["step"], old[k]),
                            )
                        )
                    # Feasibility projection: forward pass with a running
                    # lower bound; degenerate case falls back to the old
                    # vector.
                    lo = ctrl["b_min"]
                    feasible = jnp.asarray(True)
                    newv = []
                    for k in range(P - 1):
                        cap = spec.pools[k].c_max
                        feasible = feasible & (lo <= cap)
                        nk = jnp.minimum(jnp.maximum(props[k], lo), cap)
                        newv.append(nk)
                        lo = nk + 1
                    newv = jnp.where(feasible, jnp.stack(newv), old)
                    apply = fire & (ctrl["enabled"] > 0) & (wr > 0)
                    th = jnp.where(apply, newv, old)
                    moved = apply & jnp.any(newv != old)

                # Device telemetry snapshot (post-controller thresholds,
                # same ordering as the host's _window_step).
                wn = sc["win"]
                wdx = jnp.minimum(sc["wi"], win_cap - 1)

                def put(name, val):
                    return wn[name].at[wdx].set(
                        jnp.where(fire, val, wn[name][wdx])
                    )

                th_row = th if P > 1 else jnp.zeros((nb,), i32)
                wn = {
                    "t_req": put("t_req", sc["win_seen"]),
                    "now": put("now", now_t),
                    "th": put("th", th_row),
                    "queue": put("queue", queues),
                    "active": put(
                        "active", jnp.sum(frozen["nact"], axis=1, dtype=i32)
                    ),
                    "freeb": put(
                        "freeb", jnp.sum(frozen["free"], axis=1, dtype=i32)
                    ),
                    "pre": put("pre", frozen["npre"]),
                    "rej": put("rej", sc["pools"]["nrej"]),
                    "trunc": put("trunc", frozen["ntr"]),
                }
                return {
                    **sc,
                    "th": th,
                    "prev_err": jnp.where(fire, cur, sc["prev_err"]),
                    "win_prev": jnp.where(fire, sc["win_seen"], sc["win_prev"]),
                    "wi": sc["wi"] + jnp.where(fire, 1, 0),
                    "moves": sc["moves"] + jnp.where(moved, 1, 0),
                    "win": wn,
                }

            # ---- dispatch one arrival -------------------------------------
            def dispatch(sc):
                a = sc["a"]
                ai = jnp.minimum(a, n - 1)
                t = arr_t[ai]
                pidx = jax_pool_ids(sc["th"][: P - 1], bud_t[ai])
                pool_rec = sc["pool"].at[ai].set(pidx)
                st = sc["pools"]
                pg = jnp.arange(P)
                sel = pidx == pg
                alive = ig2 < lane["ninst"][:, None]
                i = jnp.argmin(jnp.where(alive, st["load"], _BIG_I), axis=1)
                # Submit-time rejection (prompt alone exceeds C_max) is
                # a pure function of the recorded pool id and the trace;
                # the record columns are folded in post-loop and only
                # the counter lives here.
                rej = inp_t[ai] >= cmax_v
                ok = sel & ~rej
                qh_i = st["qh"][pg, i]
                qt_i = st["qt"][pg, i]
                wake_i = st["wake"][pg, i]
                was_empty = qh_i < 0
                qnext = st["qnext"].at[pg, jnp.where(ok, ai, n)].set(-1)
                qnext = qnext.at[
                    pg, jnp.where(ok & ~was_empty, qt_i, n)
                ].set(ai.astype(i32))
                st = {
                    **st,
                    "qnext": qnext,
                    "qh": st["qh"].at[pg, i].set(
                        jnp.where(ok & was_empty, ai.astype(i32), qh_i)
                    ),
                    "qt": st["qt"].at[pg, i].set(
                        jnp.where(ok, ai.astype(i32), qt_i)
                    ),
                    "qlen": st["qlen"].at[pg, i].add(jnp.where(ok, 1, 0)),
                    "load": st["load"].at[pg, i].add(jnp.where(ok, 1, 0)),
                    "wake": st["wake"].at[pg, i].set(
                        jnp.where(ok & jnp.isinf(wake_i), t, wake_i)
                    ),
                    "nrej": st["nrej"] + jnp.where(sel & rej, 1, 0),
                }
                sc = {
                    **sc,
                    "a": a + 1,
                    "pools": st,
                    "pool": pool_rec,
                    "win_seen": sc["win_seen"] + 1,
                }
                if win > 0:
                    sc = window_step(sc, t)
                return sc

            # Arrival-first tie-break: dispatch while t_arr ≤ every wake
            # (matches the host heap's ``next_arrival <= next_event``).
            def disp_cond(sc):
                return (sc["a"] < n) & (
                    next_arr_at(sc["a"]) <= wake_min_all(sc["pools"])
                )

            sc = {
                "a": c["a"],
                "th": c["th"],
                "prev_err": c["prev_err"],
                "win_seen": c["win_seen"],
                "win_prev": c["win_prev"],
                "wi": c["wi"],
                "moves": c["moves"],
                "win": c["win"],
                "pool": c["pool"],
                "pools": {k: c["pools"][k] for k in _DRAIN_POOL_KEYS},
            }
            sc = lax.while_loop(disp_cond, dispatch, sc)
            return {
                **c,
                "a": sc["a"],
                "th": sc["th"],
                "prev_err": sc["prev_err"],
                "win_seen": sc["win_seen"],
                "win_prev": sc["win_prev"],
                "wi": sc["wi"],
                "moves": sc["moves"],
                "win": sc["win"],
                "pool": sc["pool"],
                "pools": {**c["pools"], **sc["pools"]},
            }

        # ---- one masked round over the stacked pools ----------------------
        def pool_round(st, rec, rejt, ctr, t_limit):
            due = st["wake"] < t_limit

            # Admission fixpoint: one wave admits/rejects at most one head
            # per due instance; loops until no instance can make progress.
            # (Instances are independent, so wave order ≡ the host's
            # per-instance sequential admission.) The carry is the slot
            # state plus the one staging column admission writes.
            def adm_masks(st_):
                stash = st_["vcnt"] > 0
                hrid = jnp.where(stash, st_["vrid"][:, :, 0], st_["qh"])
                has = due & (stash | (st_["qh"] >= 0))
                hc = jnp.clip(hrid, 0, n - 1)
                hinp = jnp.where(stash, st_["vinp"][:, :, 0], inp_t[hc])
                hpc = jnp.where(stash, st_["vpc"][:, :, 0], 0)
                need = blocks_for(hinp)
                can = st_["nact"] < nseq_v[:, None]
                rejm = has & can & (need > tblk_v[:, None])
                admm = has & can & ~rejm & (need <= st_["free"])
                return stash, hrid, hc, hinp, hpc, need, rejm, admm

            def adm_cond(val):
                st_, _, _ = val
                *_, rejm, admm = adm_masks(st_)
                return jnp.any(rejm | admm)

            def adm_body(val):
                st_, rejt_, waves_ = val
                stash, hrid, hc, hinp, hpc, need, rejm, admm = adm_masks(st_)
                prog = rejm | admm
                # pop the head (victim stash first — head-of-line order)
                pop_st = prog & stash
                pop_f = prog & ~stash

                def shiftl(arr3):
                    return jnp.concatenate(
                        [arr3[:, :, 1:], arr3[:, :, :1]], axis=2
                    )

                vrid = jnp.where(
                    pop_st[:, :, None], shiftl(st_["vrid"]), st_["vrid"]
                )
                vinp = jnp.where(
                    pop_st[:, :, None], shiftl(st_["vinp"]), st_["vinp"]
                )
                vpc = jnp.where(
                    pop_st[:, :, None], shiftl(st_["vpc"]), st_["vpc"]
                )
                nxt = jnp.take_along_axis(
                    st_["qnext"], jnp.clip(st_["qh"], 0, n), axis=1
                )
                qh = jnp.where(pop_f, nxt, st_["qh"])
                qt = jnp.where(pop_f & (nxt < 0), -1, st_["qt"])
                # admission-reject: stage the reject timestamp only (host:
                # add_one with first = finish = now); the record columns
                # fold in post-loop from rejt. One flattened scatter
                # covers every pool (request ids are disjoint across
                # pools; non-rejecting heads aim at the scratch row).
                ridx = jnp.where(rejm, hc, n)
                rejt_ = rejt_.at[ridx].set(
                    st_["wake"], mode="promise_in_bounds"
                )
                # admit into the first free slot (argmin over occupied —
                # the host's np.argmin tie-break; padded slots sit past
                # every real slot, and ``can`` already gates full pools)
                slot = jnp.argmin(st_["occ"], axis=2)
                base = st_["sqc"]
                rank = (jnp.cumsum(admm, axis=1) - admm).astype(i32)

                # One-hot admit writes: each instance fills at most one
                # slot per wave, so a masked eltwise where over (P, I, S)
                # replaces a gather + 2-update scatter pair per column —
                # XLA:CPU expands each of those into a serial while with
                # full-array boundary copies; the where fuses instead.
                sl_hot = (
                    jnp.arange(S)[None, None, :] == slot[:, :, None]
                ) & admm[:, :, None]

                def w2(arr3, val):
                    v = jnp.broadcast_to(
                        jnp.asarray(val, arr3.dtype), slot.shape
                    )
                    return jnp.where(sl_hot, v[:, :, None], arr3)

                return (
                    {
                        **st_,
                        "vrid": vrid,
                        "vinp": vinp,
                        "vpc": vpc,
                        "vcnt": st_["vcnt"] - pop_st,
                        "qh": qh,
                        "qt": qt,
                        "qlen": st_["qlen"] - prog,
                        "load": st_["load"] - rejm,
                        "nrej": st_["nrej"]
                        + jnp.sum(rejm, axis=1, dtype=i32),
                        "occ": w2(st_["occ"], True),
                        "rid": w2(st_["rid"], hrid),
                        "enq": w2(st_["enq"], arr_t[hc]),
                        "inp": w2(st_["inp"], hinp),
                        "outp": w2(st_["outp"], out_t[hc]),
                        "pre": w2(st_["pre"], hinp),
                        "rem": w2(st_["rem"], out_t[hc]),
                        "gen": w2(st_["gen"], 0),
                        "blk": w2(st_["blk"], need),
                        "ft": w2(st_["ft"], jnp.nan),
                        "tr": w2(st_["tr"], False),
                        "pc": w2(st_["pc"], hpc),
                        "sq": w2(st_["sq"], base[:, None] + rank),
                        "sqc": base + jnp.sum(admm, axis=1, dtype=i32),
                        "free": st_["free"] - jnp.where(admm, need, 0),
                        "nact": st_["nact"] + admm,
                    },
                    rejt_,
                    waves_ + 1,
                )

            with jax.named_scope("admit"):
                st, rejt, waves = lax.while_loop(
                    adm_cond, adm_body, (st, rejt, jnp.asarray(0, i32))
                )

            with jax.named_scope("advance"):
                nact = st["nact"]
                busy = due & (nact > 0)
                idle = due & ~busy
                wake_idle = jnp.where(
                    idle,
                    jnp.where(st["qlen"] > 0, st["wake"] + 1e-9, jnp.inf),
                    st["wake"],
                )
                now = jnp.where(busy, st["wake"], 0.0)
                bb = busy[:, :, None]
                occ = st["occ"]
                inp2, gen0 = st["inp"], st["gen"]
                rem0, blk0 = st["rem"], st["blk"]

                # fused decode-advance (repro.kernels.sim_decode):
                # prefill chunk + event-distance k-jump + advance +
                # completion staging, as the jnp twin (vmapped over the
                # pool axis) or the Pallas kernel (one call per pool) —
                # bit-identical paths.
                adv = advance_all(
                    t_limit,
                    busy,
                    now,
                    nact,
                    st["free"],
                    occ,
                    st["pre"],
                    st["sq"],
                    inp2,
                    gen0,
                    rem0,
                    blk0,
                    st["ft"],
                    st["tr"],
                )
                pre_arr = adv["pre"]
                dec = adv["dec"]
                end = adv["end"]
                gen_a = adv["gen"]
                rem_a = adv["rem"]
                ft_a = adv["ft"]
                trunc_n = adv["trunc_new"]
                tr_a = adv["tr"]
                comp = adv["comp"]
                ntr = st["ntr"] + jnp.sum(trunc_n, axis=(1, 2), dtype=i32)

            with jax.named_scope("record_scatter"):
                # One scatter per pool per packed record buffer, over that
                # pool's *real* ``(max_inst, n_seq)`` slot block. The
                # stacked arrays are padded to ``(P, max I, max S)``, and
                # XLA:CPU lowers a batched scatter to a serial per-row
                # loop, so scattering the padded block pays for slots that
                # can never complete (a ragged 4×128 + 12×16 topology pads
                # 4.4×). Request ids are globally unique, so per-pool
                # updates stay disjoint; non-completing slots hit the
                # scratch row. Packing same-dtype columns keeps this at
                # two scatter ops per pool per round instead of five.
                ridx = jnp.where(comp, st["rid"], n)
                recf_new = jnp.stack(
                    [ft_a, jnp.broadcast_to(end[:, :, None], (P, I, S))],
                    axis=-1,
                )
                reci_new = jnp.stack(
                    [gen_a, st["pc"], tr_a.astype(i32)], axis=-1
                )
                rf, ri = rec["recf"], rec["reci"]
                for p in range(P):
                    ip, sp = spec.pools[p].max_inst, spec.pools[p].n_seq
                    idx_p = ridx[p, :ip, :sp]
                    rf = rf.at[idx_p].set(
                        recf_new[p, :ip, :sp], mode="promise_in_bounds"
                    )
                    ri = ri.at[idx_p].set(
                        reci_new[p, :ip, :sp], mode="promise_in_bounds"
                    )
                rec = {"recf": rf, "reci": ri}

            with jax.named_scope("evict"):
                free1 = st["free"] + jnp.sum(
                    jnp.where(comp, blk0, 0), axis=2, dtype=i32
                )
                ncomp = jnp.sum(comp, axis=2, dtype=i32)

                surv = dec & (rem_a > 0) & bb
                need_s = jnp.where(surv, blocks_for(inp2 + gen_a), blk0)
                grow = jnp.where(surv, need_s - blk0, 0)
                demand = grow.sum(axis=2, dtype=i32)

                def evict_pass(_):
                    # Sort-free eviction scan. XLA:CPU sorts cost ~40 µs
                    # each inside a while body, so instead of
                    # lexsort/argsort the scan order (enq youngest-first,
                    # admission seq_no tie-break — a total order: seq_no is
                    # unique per instance) comes from pairwise-comparison
                    # ranks over the tiny (S, S) slot square, prefix sums
                    # from the same mask, and the victim stash from a
                    # rank-indexed scatter. Values are bit-identical to the
                    # sorted formulation (keys carry no NaNs and no -0/+0
                    # mix, so IEEE compare ≡ the sort's total order).
                    keyq = jnp.where(surv, -st["enq"], jnp.inf)
                    sq = st["sq"]
                    k_a, k_b = keyq[:, :, :, None], keyq[:, :, None, :]
                    sq_lt = sq[:, :, None, :] < sq[:, :, :, None]  # [a,b]: b<a
                    prec = (k_b < k_a) | ((k_b == k_a) & sq_lt)
                    rank = jnp.sum(prec, axis=3, dtype=i32)
                    le = prec | jnp.eye(S, dtype=bool)[None, None]
                    blkv = jnp.where(surv, blk0, 0)
                    cum_blk = jnp.sum(
                        jnp.where(le, blkv[:, :, None, :], 0), axis=3, dtype=i32
                    )
                    cum_grow = jnp.sum(
                        jnp.where(le, grow[:, :, None, :], 0), axis=3, dtype=i32
                    )
                    okj = (
                        demand[:, :, None] - cum_grow
                        <= free1[:, :, None] + cum_blk
                    )
                    first_ok = jnp.min(jnp.where(okj, rank, S), axis=2)
                    jsel = jnp.where(
                        demand <= free1,
                        0,
                        jnp.where(first_ok < S, first_ok + 1, 1),
                    )
                    ev = (rank < jsel[:, :, None]) & surv
                    nev = jnp.sum(ev, axis=2, dtype=i32)

                    # victims → stash, in admission (seq_no) order, ahead of
                    # the previous stash (requeue-at-head semantics). The
                    # permutation runs as one-hot select-reduces over the
                    # (S, S) square instead of gather/scatter: XLA:CPU's
                    # batched scatter and gather both cost ~50 µs inside a
                    # while body versus ~10 µs for the masked reduce, and
                    # the one-hot sums are exact (one source per slot).
                    vrank = jnp.sum(ev[:, :, None, :] & sq_lt, axis=3, dtype=i32)
                    rr = jnp.arange(S)
                    in_new = rr[None, None, :] < nev[:, :, None]
                    # vm[j, a]: stash slot j takes the victim in slot a
                    # (the one whose victim-rank is j); om[j, a]: slot j
                    # takes previous-stash slot a = j − n_victims.
                    vm = (
                        ev[:, :, None, :]
                        & (vrank[:, :, None, :] == rr[None, None, :, None])
                        & in_new[:, :, :, None]
                    )
                    om = (
                        rr[None, None, None, :]
                        == rr[None, None, :, None] - nev[:, :, None, None]
                    ) & ~in_new[:, :, :, None]

                    def stash(old3, vals):
                        return jnp.sum(
                            jnp.where(vm, vals[:, :, None, :], 0),
                            axis=3,
                            dtype=i32,
                        ) + jnp.sum(
                            jnp.where(om, old3[:, :, None, :], 0),
                            axis=3,
                            dtype=i32,
                        )

                    vr = stash(st["vrid"], st["rid"])
                    vi = stash(st["vinp"], inp2 + gen_a)
                    vp = stash(st["vpc"], st["pc"] + 1)
                    return ev, nev, vr, vi, vp

                def no_evict(_):
                    # demand ≤ free everywhere ⇒ jsel = 0 ⇒ nothing evicts
                    # and the stash is untouched — same values, no sorts.
                    return (
                        jnp.zeros((P, I, S), bool),
                        jnp.zeros((P, I), i32),
                        st["vrid"],
                        st["vinp"],
                        st["vpc"],
                    )

                need = jnp.any(demand > free1)
                if gate:
                    evict, nevict, vrid, vinp, vpc = lax.cond(
                        need, evict_pass, no_evict, None
                    )
                else:
                    evict, nevict, vrid, vinp, vpc = evict_pass(None)
                npre = st["npre"] + jnp.sum(evict, axis=(1, 2), dtype=i32)
                free1 = free1 + jnp.sum(
                    jnp.where(evict, blk0, 0), axis=2, dtype=i32
                )

            with jax.named_scope("round_update"):
                keep = surv & ~evict
                free1 = free1 - jnp.sum(
                    jnp.where(keep, grow, 0), axis=2, dtype=i32
                )
                cleared = comp | evict
                nact_a = nact - ncomp - nevict
                qlen_a = st["qlen"] + nevict
                alive_r = (nact_a > 0) | (qlen_a > 0)

                st = {
                    **st,
                    "occ": jnp.where(bb, occ & ~cleared, occ),
                    "pre": pre_arr,
                    "rem": jnp.where(bb, rem_a, rem0),
                    "gen": jnp.where(bb, gen_a, gen0),
                    "blk": jnp.where(
                        bb, jnp.where(cleared, 0, jnp.where(keep, need_s, blk0)), blk0
                    ),
                    "ft": jnp.where(bb, ft_a, st["ft"]),
                    "tr": jnp.where(bb, tr_a, st["tr"]),
                    "vrid": jnp.where(bb, vrid, st["vrid"]),
                    "vinp": jnp.where(bb, vinp, st["vinp"]),
                    "vpc": jnp.where(bb, vpc, st["vpc"]),
                    "vcnt": jnp.where(busy, st["vcnt"] + nevict, st["vcnt"]),
                    "free": jnp.where(busy, free1, st["free"]),
                    "nact": jnp.where(busy, nact_a, nact),
                    "qlen": jnp.where(busy, qlen_a, st["qlen"]),
                    "load": jnp.where(busy, st["load"] - ncomp, st["load"]),
                    "wake": jnp.where(
                        busy, jnp.where(alive_r, end, jnp.inf), wake_idle
                    ),
                    "npre": npre,
                    "ntr": ntr,
                }

                # Loop counters: scalars beside the state update (the live
                # slot sum is one (P, I) reduce of the post-admission nact).
                ran = need.astype(i32) if gate else 1
                ctr = {
                    "rounds": ctr["rounds"] + 1,
                    "adm_waves": ctr["adm_waves"] + executed(waves),
                    "evict_runs": ctr["evict_runs"] + ran,
                    "evict_need": ctr["evict_need"]
                    + executed(need.astype(i32)),
                    "live_slot_rounds": ctr["live_slot_rounds"]
                    + jnp.sum(nact, dtype=jnp.int64),
                }
            return st, rec, rejt, ctr

        # ---- outer epoch loop: drain arrivals, then sweep rounds ----------
        def cond_fn(c):
            return (c["a"] < n) | jnp.isfinite(wake_min_all(c["pools"]))

        def one_round(c, t_limit):
            pools_s, rec_s, rejt_s, ctr_s = pool_round(
                c["pools"], c["rec"], c["rejt"], c["ctr"], t_limit
            )
            return {
                **c,
                "pools": pools_s,
                "rec": rec_s,
                "rejt": rejt_s,
                "ctr": ctr_s,
            }

        if gate:

            def body_fn(c):
                c = drain(c)
                # Coalesced sweep: run rounds back-to-back until the
                # next arrival (t_limit is loop-invariant — `a` doesn't
                # move during a sweep), instead of re-entering the outer
                # body per round. The sweep carry is the slot state +
                # the completion-written record columns + the counters.
                t_limit = next_arr_at(c["a"])

                def sweep_cond(s):
                    return wake_min_all(s[0]) < t_limit

                def sweep_body(s):
                    pools_s, rec_s, rejt_s, ctr_s = s
                    cs = one_round(
                        {
                            **c,
                            "pools": pools_s,
                            "rec": rec_s,
                            "rejt": rejt_s,
                            "ctr": ctr_s,
                        },
                        t_limit,
                    )
                    return (cs["pools"], cs["rec"], cs["rejt"], cs["ctr"])

                pools_s, rec_s, rejt_s, ctr_s = lax.while_loop(
                    sweep_cond,
                    sweep_body,
                    (c["pools"], c["rec"], c["rejt"], c["ctr"]),
                )
                return {
                    **c,
                    "pools": pools_s,
                    "rec": rec_s,
                    "rejt": rejt_s,
                    "ctr": ctr_s,
                    "iters": c["iters"] + 1,
                }

        else:

            def body_fn(c):
                # Vmapped lanes: drain arrivals, then exactly ONE round
                # per outer iteration. A nested sweep loop (rounds
                # back-to-back until the next arrival) would run to the
                # max round count over lanes per epoch — Σ_epochs
                # max_lanes ≫ max_lanes Σ_epochs once lanes diverge, a
                # measured 5.6× blowup on the 16-lane threshold sweep —
                # while a flat one-action ``lax.cond`` pays both branch
                # bodies plus two full-carry selects per iteration under
                # vmap. One unconditional round per outer step keeps
                # lockstep losses near zero (arrival streams are shared
                # across lanes, so the drain while stays synchronized)
                # and a lane with nothing due runs a masked no-op round
                # — bit-identical, modulo the scratch record row.
                c = drain(c)
                c = one_round(c, next_arr_at(c["a"]))
                return {**c, "iters": c["iters"] + 1}

        c0 = {
            "a": jnp.asarray(0, i32),
            "pools": _init_pools(spec, n),
            "rec": {"recf": rec0["recf"], "reci": rec0["reci"]},
            "pool": rec0["pool"],
            "rejt": rec0["rejt"],
            "th": lane["th"],
            "prev_err": jnp.zeros((P,), i32),
            "win_seen": jnp.asarray(0, i32),
            "win_prev": jnp.asarray(0, i32),
            "wi": jnp.asarray(0, i32),
            "moves": jnp.asarray(0, i32),
            "iters": jnp.asarray(0, i32),
            "ctr": _init_counters(),
            "win": _init_windows(P, nb, win_cap),
        }
        c = lax.while_loop(cond_fn, body_fn, c0)

        # ---- post-loop record folding -------------------------------------
        # Admission rejects: staged timestamp is finite. Submit rejects:
        # the prompt alone exceeds the recorded pool's C_max. Both write
        # first = finish = reject time, exactly as the host's add_one.
        # The fold runs at full (n+1,) length so the outputs can alias the
        # donated input buffers (the scratch row n is sliced off on the
        # host; its folded value is meaningless).
        with jax.named_scope("fold"):
            arr_p = jnp.concatenate([arr_t, jnp.zeros((1,), f64)])
            inp_p = jnp.concatenate([inp_t, jnp.zeros((1,), i32)])
            rejt = c["rejt"]
            arej = jnp.isfinite(rejt)
            srej = inp_p >= cmax_v[c["pool"]]
            rejm = arej | srej
            # Rejected rows get first = finish = reject time, so the fold
            # is one masked where over the packed f64 buffer (the output
            # keeps the donated buffer's (n + 1, 2) shape and aliases it).
            recf_full = jnp.where(
                rejm[:, None],
                jnp.where(arej, rejt, arr_p)[:, None],
                c["rec"]["recf"],
            )
            rec_full = {
                "recf": recf_full,
                "reci": c["rec"]["reci"],
                "pool": c["pool"],
                "rejt": rejt,
                "rej": rejm,
            }
            first = recf_full[:n, 0]
            finish = recf_full[:n, 1]
            out_tok = rec_full["reci"][:n, 0]
            trunc = rec_full["reci"][:n, 2]
            pool_c = c["pool"][:n]

            compm = ~rejm[:n]
            ttft = jnp.where(compm, first - arr_t, jnp.nan)
            tpot = jnp.where(
                compm & (out_tok > 1),
                (finish - first) / jnp.maximum(out_tok - 1, 1),
                jnp.nan,
            )
            out = {
                "metrics": {
                    "completed": jnp.sum(compm),
                    "rejected": jnp.sum(rejm[:n]),
                    "truncated": jnp.sum(trunc),
                    "routed": jnp.stack(
                        [jnp.sum(pool_c == p) for p in range(P)]
                    ),
                    "ttft_mean": jnp.nanmean(ttft),
                    "ttft_p50": jnp.nanpercentile(ttft, 50),
                    "ttft_p99": jnp.nanpercentile(ttft, 99),
                    "tpot_mean": jnp.nanmean(tpot),
                    "tpot_p99": jnp.nanpercentile(tpot, 99),
                    "t_end": jnp.max(finish),
                    "makespan": jnp.max(finish) - jnp.min(arr_t),
                },
                "preempt": c["pools"]["npre"],
                "reject": c["pools"]["nrej"],
                "truncate": c["pools"]["ntr"],
                "th": c["th"],
                "moves": c["moves"],
                "nwin": c["wi"],
                "win": c["win"],
                "iters": c["iters"],
                **c["ctr"],
            }
            if return_records:
                # Full (n + 1,) leaves so every output can alias its donated
                # input buffer; callers slice off the scratch row.
                out["rec"] = rec_full
        return out

    return core


@functools.lru_cache(maxsize=None)
def _runner(
    spec: _SimSpec,
    n: int,
    return_records: bool,
    grid: bool,
):
    """Cached jitted simulation, specialized per (spec, n, outputs, vmap).

    The third argument (record buffers) is donated — XLA writes the
    scatters into the caller's buffers in place."""
    if not grid:
        return jax.jit(_make_core(spec, n, return_records), donate_argnums=(2,))
    core = _make_core(spec, n, return_records, lanes="lanes")
    fn = jax.vmap(core, in_axes=(None, 0, 0), axis_name="lanes")
    return jax.jit(fn, donate_argnums=(2,))


# ---------------------------------------------------------------------------
# AOT executable cache + probes
# ---------------------------------------------------------------------------

#: {(spec, n, return_records, grid, g): {"lower_s", "compile_s"}}
_COMPILE_STATS: dict = {}

#: Counters and host spans of the most recent compiled run (see
#: :func:`last_run_stats`).
_LAST_RUN: dict = {}

#: Host spans of one run: the parent, then its children in call order.
_RUN_SPANS = (
    "repro.sim.run",
    "repro.sim.route_precompute",
    "repro.sim.compile",
    "repro.sim.stage",
    "repro.sim.device_run",
    "repro.sim.results",
)

_CALLS = itertools.count(1)


def _run_span(mode: str):
    """Decorate a run entry: a fresh ``_LAST_RUN`` (every span at 0 s) and
    the parent span ``repro.sim.run`` over the whole call."""

    def deco(fn):
        @functools.wraps(fn)
        def entry(*args, **kwargs):
            spans = dict.fromkeys(_RUN_SPANS, 0.0)
            _LAST_RUN.clear()
            _LAST_RUN.update(mode=mode, call=next(_CALLS), spans=spans)
            with _span("repro.sim.run", spans, mode=mode):
                return fn(*args, **kwargs)

        return entry

    return deco


def _note_counters(out: dict, spec: _SimSpec, g: int) -> None:
    """Copy one run's loop counters into ``_LAST_RUN``.

    Lane maxima are what the device executed; ``live_slot_rounds`` and
    ``rounds_total`` sum over lanes (``last_run_stats`` has the keys)."""
    P = len(spec.pools)
    I = max(ps.max_inst for ps in spec.pools)
    S = max(ps.n_seq for ps in spec.pools)
    _LAST_RUN.update(
        g=g,
        slot_rows=P * I * S,
        iters=int(np.max(out["iters"])),
        **{
            k: int(np.max(out[k]))
            for k in ("rounds", "adm_waves", "evict_runs", "evict_need")
        },
        rounds_total=int(np.sum(out["rounds"])),
        live_slot_rounds=int(np.sum(out["live_slot_rounds"])),
    )


@contextlib.contextmanager
def _span(name: str, into: dict, key: Optional[str] = None, **meta):
    """Time a block on the profiler's clock and on ``perf_counter``.

    Opens a ``jax.profiler.TraceAnnotation`` (a host span in a device
    profile, with the run's call number and ``meta``) and adds the block's
    seconds to ``into[key or name]``, so the span and its counter come
    from one timing. Without a profiler running it costs microseconds."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(
            name, call=_LAST_RUN.get("call", 0), **meta
        ):
            yield
    finally:
        k = key or name
        into[k] = into.get(k, 0.0) + (time.perf_counter() - t0)


def _abstract_inputs(spec: _SimSpec, n: int, grid: bool, g: int):
    """ShapeDtypeStructs matching the runtime arguments of ``_runner``."""
    P = len(spec.pools)
    sds = jax.ShapeDtypeStruct

    def L(shape, dt):
        return sds(((g,) + shape) if grid else shape, dt)

    trace = {
        "arr": sds((n,), np.float64),
        "inp": sds((n,), np.int32),
        "outp": sds((n,), np.int32),
        "budget": sds((n,), np.int32),
    }
    lane = {
        "th": L((P - 1,), np.int32),
        "ninst": L((P,), np.int32),
        "ctrl": {
            "enabled": L((), np.int32),
            "b_min": L((), np.int32),
            "step": L((), np.int32),
            "factor": L((), np.float32),
            "err_hi": L((), np.float32),
            "over_hi": L((), np.float32),
        },
    }
    rec = {
        name: L((n + 1,) if w == 1 else (n + 1, w), dt)
        for name, dt, w in _REC_DTYPES
    }
    return trace, lane, rec


@functools.lru_cache(maxsize=None)
def _aot(
    spec: _SimSpec,
    n: int,
    return_records: bool,
    grid: bool,
    g: int,
):
    """AOT-compiled executable for one static shape key.

    ``.lower().compile()`` runs here exactly once per key, under the
    host spans ``repro.sim.lower`` and ``repro.sim.backend_compile``; their
    wall-clock times land in ``_COMPILE_STATS`` so the benchmark's
    ``jax_compile`` row can report compilation alone (no run attached).
    """
    with jax.enable_x64(), warnings.catch_warnings():
        if not return_records:
            # Without record outputs the donated buffers have no output
            # to alias into — donation still lets XLA recycle them as
            # in-loop scratch, so the "not usable" note is expected.
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
        fn = _runner(spec, n, return_records, grid)
        targs, lane, rec = _abstract_inputs(spec, n, grid, g)
        stats = {}
        with _span("repro.sim.lower", stats, "lower_s", n=n, g=g):
            lowered = fn.lower(targs, lane, rec)
        with _span("repro.sim.backend_compile", stats, "compile_s", n=n, g=g):
            compiled = lowered.compile()
    _COMPILE_STATS[(spec, n, return_records, grid, g)] = stats
    return compiled


def last_run_stats() -> dict:
    """Loop counters and host spans of the most recent compiled run.

    ``mode`` (``"fleet"``/``"grid"``), ``n``, ``g`` (1 for a single
    lane), ``call`` (this process's run number, also on every span) and
    ``slot_rows`` (P x max I x max S, the padded slot rows each round
    works over). Loop counters, as the device executed them (under vmap
    the lanes run in lockstep until the last is done, so a grid reports
    the lane maximum unless noted):

    * ``iters``: outer epochs (coalesced bound ``n + 1``);
    * ``rounds``: sweep rounds (≈ the pre-coalescing outer iteration
      count); a grid adds ``rounds_total``, summed over lanes;
    * ``adm_waves``: trips of the admission fixpoint;
    * ``evict_runs``: rounds in which the eviction pass ran (every round
      on a grid, the gate's taken branch on a single lane);
    * ``evict_need``: rounds in which some instance's growth demand
      exceeded its free blocks (in any lane, on a grid);
    * ``live_slot_rounds``: live decode slots after admission, summed over
      each lane's own rounds and over lanes, so
      ``live_slot_rounds / (g * rounds * slot_rows)`` is the share of the
      slot rows each executed round works on that hold a live request.

    ``spans`` holds the seconds of each host span of the run:
    ``repro.sim.run`` (the whole entry call) and its children
    ``repro.sim.route_precompute``, ``repro.sim.compile`` (getting the
    executable: an ``_aot`` miss's lower and compile, microseconds on a
    hit), ``repro.sim.stage`` (host input arrays), ``repro.sim.device_run``
    (the executable call until its outputs are on the host) and
    ``repro.sim.results`` (record unpack, back-fill, history, telemetry,
    summaries). ``run - device_run`` is the host's own time in the call.
    """
    return {**_LAST_RUN, "spans": dict(_LAST_RUN.get("spans", {}))}


def compile_stats() -> list[dict]:
    """Every AOT compilation this process paid, with readable keys.

    One dict per ``_aot`` cache entry: ``n``, ``return_records``,
    ``grid``, ``g`` plus the measured ``lower_s`` /
    ``compile_s`` walls. Benchmarks use this to report grid-executable
    compile time without re-deriving the cache key."""
    return [
        {
            "n": k[1],
            "return_records": k[2],
            "grid": k[3],
            "g": k[4],
            **v,
        }
        for k, v in _COMPILE_STATS.items()
    ]


def carry_report(fleet, trace) -> dict:
    """Byte sizes of the compiled loop carries for one (fleet, trace).

    Shapes come from ``jax.eval_shape`` over the carry constructors (no
    tracing of the loop itself). ``record_bytes`` is the donated buffer
    set, which no longer rides the outer/drain carries."""
    cols = _as_columns(trace)
    spec, _, _ = _fleet_spec(fleet, cols)
    return _carry_report(spec, len(cols))


def _carry_report(spec: _SimSpec, n: int) -> dict:
    P = len(spec.pools)
    win = spec.win_size
    win_cap = (n // win + 2) if win > 0 else 1
    nb = max(P - 1, 1)

    def nbytes(tree) -> int:
        return int(
            sum(
                int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
                for leaf in jax.tree_util.tree_leaves(tree)
            )
        )

    with jax.enable_x64():
        pools = jax.eval_shape(lambda: _init_pools(spec, n))
        wins = jax.eval_shape(lambda: _init_windows(P, nb, win_cap))
        ctr = nbytes(jax.eval_shape(_init_counters))
    rec_bytes = sum(
        (n + 1) * w * np.dtype(dt).itemsize for _, dt, w in _REC_DTYPES
    )
    sweep_rec = sum(
        (n + 1) * w * np.dtype(dt).itemsize
        for name, dt, w in _REC_DTYPES
        if name in ("recf", "reci", "rejt")
    )
    i4 = np.dtype(np.int32).itemsize
    scalars = 6 * i4 + ctr  # a, win_seen, win_prev, wi, moves, iters
    th_bytes = (P - 1) * i4 + P * i4  # th + prev_err
    drain_pools = nbytes({k: pools[k] for k in _DRAIN_POOL_KEYS})
    drain = (
        drain_pools
        + nbytes(wins)
        + (n + 1) * i4  # pool column
        + th_bytes
        + 5 * i4  # a, win_seen, win_prev, wi, moves
    )
    sweep = nbytes(pools) + sweep_rec + ctr
    outer = nbytes(pools) + nbytes(wins) + rec_bytes + th_bytes + scalars
    return {
        "carry_bytes": outer,
        "drain_carry_bytes": drain,
        "sweep_carry_bytes": sweep,
        "record_bytes": rec_bytes,
    }


def aot_compile(fleet, trace) -> dict:
    """Compile the single-lane executable for (fleet, trace) ahead of time.

    Returns the ``_COMPILE_STATS`` entry (``lower_s``, ``compile_s``)
    plus ``cached`` (True when the executable already existed, i.e. the
    times are from the original compilation). The subsequent
    ``run_fleet`` call for the same shape hits the cache and pays no
    compilation."""
    cols = _as_columns(trace)
    spec, _, _ = _fleet_spec(fleet, cols)
    key = (spec, len(cols), True, False, 0)
    cached = key in _COMPILE_STATS
    with jax.enable_x64():
        _aot(*key)
    stats = dict(_COMPILE_STATS[key])
    stats["cached"] = cached
    return stats


# ---------------------------------------------------------------------------
# Host-side routing precompute
# ---------------------------------------------------------------------------


def precompute_budget_trajectory(
    cols: TraceColumns,
    calibrator: EmaCalibrator,
    *,
    epoch_cap: int,
):
    """Per-request estimated budgets with epoch-lagged EMA feedback.

    Mirrors the vectorized backend's ramped routing epochs (64 doubling to
    ``epoch_cap``): requests in one epoch route with the EMA state as of
    the epoch start, then the epoch's observations fold in through the
    cached ``lax.scan`` kernel. The device loop then only needs a
    ``searchsorted`` per dispatch — thresholds stay honest vmap axes while
    the float EMA never enters the compiled loop. Approximation vs the
    host: observations fold in *arrival* order (host folds completions),
    which the routed-tolerance test class bounds.

    Both the estimate and the EMA fold go through the cached kernel
    factories (``("estimate", chunk, γ)`` / ``("observe", chunk, β)`` in
    ``kernel_trace_counts()``): epochs are padded to their ramp width, so
    the whole precompute compiles a handful of shapes once per process
    instead of dispatching eager ops per chunk. Padding rows carry
    ``prompt_tokens=0`` and are sliced off before use, so the budgets and
    the final EMA state are bit-identical to the unpadded fold.

    Returns ``(budgets int32 (n,), final CalibState)``.
    """
    n = len(cols)
    budgets = np.zeros(n, dtype=np.int32)
    state = calibrator.to_state()
    gamma = float(calibrator.gamma)
    beta = float(calibrator.beta)
    chunk = min(64, epoch_cap)
    pos = 0
    while pos < n:
        start = pos
        width = chunk  # kernel shape for this epoch (pre-ramp)
        pos = min(n, pos + chunk)
        chunk = min(epoch_cap, chunk * 2)
        m = pos - start
        pad = width - m
        cat = jnp.asarray(
            np.pad(np.asarray(cols.category[start:pos]), (0, pad)), jnp.int32
        )
        est = _estimate_budget_kernel(width, gamma)
        budgets[start:pos] = np.asarray(
            est(
                state,
                jnp.asarray(
                    np.pad(np.asarray(cols.byte_len[start:pos]), (0, pad))
                ),
                jnp.asarray(
                    np.pad(
                        np.asarray(cols.max_output_tokens[start:pos]), (0, pad)
                    )
                ),
                cat,
            )
        )[:m]
        upd = _update_stream_kernel(width, beta)
        state = upd(
            state,
            jnp.asarray(
                np.pad(
                    np.asarray(cols.byte_len[start:pos], np.float32), (0, pad)
                ),
                jnp.float32,
            ),
            jnp.asarray(
                np.pad(
                    np.asarray(
                        cols.true_input_tokens[start:pos], np.float32
                    ),
                    (0, pad),
                ),
                jnp.float32,
            ),
            cat,
        )
    return budgets, state


def _trace_arrays(cols: TraceColumns, budgets: Optional[np.ndarray]):
    n = len(cols)
    return {
        "arr": np.asarray(cols.arrival_time, np.float64),
        "inp": np.asarray(cols.true_input_tokens, np.int32),
        "outp": np.asarray(cols.true_output_tokens, np.int32),
        "budget": (
            np.zeros(n, np.int32) if budgets is None else budgets
        ),
    }


def _ctrl_params(controller, enabled: bool):
    """Controller gains as a traced scalar dict (a vmappable lane axis)."""
    if controller is None:
        return {
            "enabled": np.int32(0),
            "b_min": np.int32(512),
            "step": np.int32(DEFAULT_INCREASE_STEP),
            "factor": np.float32(DEFAULT_DECREASE_FACTOR),
            "err_hi": np.float32(DEFAULT_ERROR_RATE_HI),
            "over_hi": np.float32(DEFAULT_OVERLOAD_RATIO_HI),
        }
    return {
        "enabled": np.int32(1 if enabled else 0),
        "b_min": np.int32(controller.b_min),
        "step": np.int32(controller.increase_step),
        "factor": np.float32(controller.decrease_factor),
        "err_hi": np.float32(controller.error_rate_hi),
        "over_hi": np.float32(controller.overload_ratio_hi),
    }


def _as_columns(trace) -> TraceColumns:
    return (
        trace
        if isinstance(trace, TraceColumns)
        else TraceColumns.from_requests(trace)
    ).sorted_by_arrival()


def _fleet_spec(fleet, cols: TraceColumns):
    """Build the static spec for a live FleetSim (shared with the probes)."""
    ordered = sorted(fleet._pool_index, key=fleet._pool_index.get)
    shells = [fleet.pools[name] for name in ordered]
    spec = _SimSpec(
        # Capacities come from the live shells (not recomputed from the
        # config) so post-construction total_blocks overrides are honored.
        pools=tuple(
            _PoolSpec(
                name=name,
                c_max=int(s.config.c_max),
                n_seq=int(s.config.n_seq),
                total_blocks=int(s.total_blocks),
                max_inst=int(s.num_instances),
            )
            for name, s in zip(ordered, shells)
        ),
        w=float(fleet.timing.w_base),
        h=float(fleet.timing.h_per_seq),
        prefill_chunk=int(fleet.timing.prefill_chunk),
        win_size=int(fleet._win_size),
    )
    return spec, ordered, shells


# ---------------------------------------------------------------------------
# FleetSim backend entry (single lane)
# ---------------------------------------------------------------------------


@_run_span("fleet")
def run_fleet_jax(fleet, trace):
    """Execute one fleet run on the compiled backend; returns FleetResult.

    Called by ``FleetSim.run`` for ``backend="jax"``. The fleet's
    ``VectorPoolSim`` shells receive the device-computed records and
    counters afterwards, so ``fleet.pools[name].record_arrays()``,
    telemetry replay, and ``router.stats()`` all behave like a host run.
    """
    # Import here: fleet imports this module lazily, and metrics/fleet
    # are imported lazily here, to keep the module graph acyclic.
    from repro.sim.fleet import FleetResult
    from repro.sim.metrics import summarize_columns

    spans = _LAST_RUN["spans"]
    cols = _as_columns(trace)
    n = len(cols)
    _LAST_RUN["n"] = n
    spec, ordered, shells = _fleet_spec(fleet, cols)
    P = len(spec.pools)

    router = fleet.router
    budgets = None
    if router is not None and n:
        epoch_cap = (
            fleet.epoch
            if fleet.controller is None
            else max(1, min(fleet.epoch, fleet.control_window))
        )
        with _span("repro.sim.route_precompute", spans, n=n, g=1):
            budgets, final_state = precompute_budget_trajectory(
                cols, router.calibrator, epoch_cap=epoch_cap
            )
        router.calibrator.load_state(final_state)
        th0 = [int(b) for b in router.pools.thresholds]
    else:
        th0 = []

    if self_telemetry := fleet.telemetry:
        self_telemetry.set_trace(
            cols.byte_len, cols.category, cols.true_input_tokens,
            cols.max_output_tokens,
        )

    if n == 0:
        empty = {k: np.empty(0, dt) for k, dt in (
            ("request_id", np.int64), ("arrival", np.float64),
            ("first_token", np.float64), ("finish", np.float64),
            ("output_tokens", np.int64), ("preemptions", np.int64),
            ("truncated", bool), ("rejected", bool),
        )}
        return FleetResult(
            summary=summarize_columns("fleet", empty),
            per_pool={name: summarize_columns(name, empty) for name in ordered},
            router_stats=router.stats() if router else {},
            preemptions=0, rejections=0, truncations=0,
            telemetry=fleet.telemetry, slo=fleet.slo,
        )

    with jax.enable_x64():
        with _span("repro.sim.compile", spans, n=n, g=1):
            exe = _aot(spec, n, True, False, 0)
        with _span("repro.sim.stage", spans, n=n, g=1):
            lane = {
                "th": np.asarray(th0, np.int32),
                "ninst": np.asarray(
                    [fleet.pools[name].num_instances for name in ordered],
                    np.int32,
                ),
                "ctrl": _ctrl_params(fleet.controller, enabled=True),
            }
            args = (_trace_arrays(cols, budgets), lane, _fresh_records(n))
        with _span("repro.sim.device_run", spans, n=n, g=1):
            out = jax.tree_util.tree_map(np.asarray, exe(*args))

    with _span("repro.sim.results", spans, n=n, g=1):
        _note_counters(out, spec, 1)
        rec = _unpack_records(out["rec"], n)
        ids = np.asarray(cols.request_id, np.int64)
        arr = np.asarray(cols.arrival_time, np.float64)
        fleet_cols = {
            "request_id": ids,
            "arrival": arr,
            "first_token": rec["first"],
            "finish": rec["finish"],
            "output_tokens": rec["out"].astype(np.int64),
            "preemptions": rec["pre"].astype(np.int64),
            "truncated": rec["trunc"],
            "rejected": rec["rej"],
        }
        per_pool_cols = {}
        for idx, name in enumerate(ordered):
            m = rec["pool"] == idx
            pc = {k: v[m] for k, v in fleet_cols.items()}
            per_pool_cols[name] = pc
            shell = shells[idx]
            shell._records.add_bulk(*(pc[k] for k, _ in shell._records.COLUMNS))
            shell.preemption_count = int(out["preempt"][idx])
            shell.rejection_count = int(out["reject"][idx])
            shell.truncation_count = int(out["truncate"][idx])
            if router is not None:
                router.routed[name] += int(out["metrics"]["routed"][idx])

        final_th = [int(b) for b in out["th"][: P - 1]]
        if router is not None and fleet.controller is not None:
            router.pools.set_thresholds(final_th)
            _synthesize_history(fleet.controller, out, th0)

        t_end = float(out["metrics"]["t_end"])
        if fleet.telemetry is not None:
            _replay_telemetry(fleet, ordered, shells, spec, out, n, t_end, final_th)

        return FleetResult(
            summary=summarize_columns("fleet", fleet_cols),
            per_pool={
                name: summarize_columns(name, c)
                for name, c in per_pool_cols.items()
            },
            router_stats=router.stats() if router else {},
            preemptions=int(out["preempt"].sum()),
            rejections=int(out["reject"].sum()),
            truncations=int(out["truncate"].sum()),
            telemetry=fleet.telemetry,
            slo=fleet.slo,
        )


def _synthesize_history(controller, out, th0):
    """Rebuild a BoundaryMove trajectory from the device window snapshots.

    The device loop records the post-controller threshold vector at every
    window; diffing consecutive snapshots recovers when each boundary
    moved and to what value. The AIMD input signals are not re-derived —
    moves carry reason "device"."""
    nwin = int(out["nwin"])
    prev = list(th0)
    for w in range(nwin):
        cur = [int(b) for b in out["win"]["th"][w][: len(prev)]]
        for k, (a, b) in enumerate(zip(prev, cur)):
            if a != b:
                controller.history.append(
                    BoundaryMove(
                        t=int(out["win"]["t_req"][w]),
                        boundary=k,
                        value=b,
                        reason="device",
                    )
                )
        prev = cur


def _replay_telemetry(fleet, ordered, shells, spec, out, n, t_end, final_th):
    """Replay device window snapshots into the host FleetTelemetry.

    Same windows, same sampling order (controller's thresholds first,
    then the sample) as the host backends. Counter columns come from the
    device's cumulative per-pool counters; gauges (queue depth, active,
    kv_frac) from the snapshot state. The calibration-error series uses
    the final EMA state for every window (the device run does not carry
    the float EMA) — documented approximation."""
    telemetry = fleet.telemetry
    win = out["win"]
    nwin = int(out["nwin"])
    router = fleet.router
    prev_req = 0
    for name, shell in zip(ordered, shells):
        shell.blocks_free = np.zeros(shell.num_instances, dtype=np.int64)
    for w in range(nwin):
        for idx, shell in enumerate(shells):
            shell.preemption_count = int(win["pre"][w, idx])
            shell.rejection_count = int(win["rej"][w, idx])
            shell.truncation_count = int(win["trunc"][w, idx])
            shell.state.queue_depth = int(win["queue"][w, idx])
            shell.state.active = int(win["active"][w, idx])
            shell.blocks_free[:] = 0
            shell.blocks_free[0] = int(win["freeb"][w, idx])
        if router is not None and fleet.controller is not None:
            router.pools.set_thresholds(
                [int(b) for b in win["th"][w][: len(router.pools) - 1]]
            )
        t_req = int(win["t_req"][w])
        telemetry.sample(
            t_req=t_req, now=float(win["now"][w]), lo=prev_req, hi=t_req
        )
        prev_req = t_req
    # final flush (host _finish_windows): drained end state
    for idx, shell in enumerate(shells):
        shell.preemption_count = int(out["preempt"][idx])
        shell.rejection_count = int(out["reject"][idx])
        shell.truncation_count = int(out["truncate"][idx])
        shell.state.queue_depth = 0
        shell.state.active = 0
        shell.blocks_free[:] = spec.pools[idx].total_blocks
    if router is not None and fleet.controller is not None:
        router.pools.set_thresholds(final_th)
    telemetry.sample(t_req=n, now=t_end, lo=prev_req, hi=n)


# ---------------------------------------------------------------------------
# Vmapped sensitivity grids
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FleetGridResult:
    """Columnar results of one vmapped fleet sweep (G grid lanes).

    Per-lane reductions are computed on device over the *full* run (no
    warm-up discard — grid metrics are for relative comparisons across
    lanes; use a single-lane ``FleetSim`` run for paper-grade numbers).
    Percentiles are linear-interpolation (``jnp.nanpercentile``), not the
    nearest-rank convention of :func:`repro.sim.metrics.summarize`.
    """

    pool_names: tuple[str, ...]
    thresholds: np.ndarray  # (G, P-1) initial boundary vectors
    instances: np.ndarray  # (G, P) instance counts
    completed: np.ndarray  # (G,)
    rejected: np.ndarray  # (G,)
    truncated: np.ndarray  # (G,)
    preemptions: np.ndarray  # (G,) fleet total
    routed: np.ndarray  # (G, P) dispatches per pool
    ttft_mean: np.ndarray
    ttft_p50: np.ndarray
    ttft_p99: np.ndarray
    tpot_mean: np.ndarray
    tpot_p99: np.ndarray
    makespan: np.ndarray  # (G,) max finish − min arrival
    final_thresholds: np.ndarray  # (G, P-1) post-controller vectors
    controller_moves: np.ndarray  # (G,)
    #: (G, n) per-request record arrays when ``return_records=True``.
    records: Optional[dict] = None

    def __len__(self) -> int:
        return len(self.completed)

    def goodput(self) -> np.ndarray:
        """Completed non-truncated requests per second, per lane."""
        span = np.maximum(self.makespan, 1e-12)
        return (self.completed - self.truncated) / span


def _broadcast_axis(values, g: int, name: str):
    if len(values) == 1:
        return [values[0]] * g
    if len(values) != g:
        raise ValueError(
            f"grid axis {name!r} has length {len(values)}, expected 1 or {g}"
        )
    return list(values)


@_run_span("grid")
def run_fleet_grid(
    trace,
    pools: dict[str, tuple[PoolConfig, int]],
    timing: TimingModel,
    *,
    thresholds: Optional[Sequence[Sequence[int]]] = None,
    instances: Optional[Sequence[Sequence[int]]] = None,
    gains: Optional[Sequence[Optional[dict]]] = None,
    b_short: int = 8192,
    calibrator: Optional[EmaCalibrator] = None,
    epoch: int = 2048,
    control_window: int = 512,
    return_records: bool = False,
) -> FleetGridResult:
    """Run a whole sensitivity sweep as ONE vmapped device program.

    Grid axes (all optional, zip semantics — length G or 1, broadcast):

    ``thresholds``
        Sequence of boundary vectors (each length P−1, pool-budget order).
    ``instances``
        Sequence of per-pool instance-count vectors (length P). Lanes run
        padded to the max count with dead-lane masking, so mixed fleet
        sizes share one compiled program.
    ``gains``
        Sequence of AIMD controller parameter dicts (keys ``b_min``,
        ``increase_step``, ``decrease_factor``, ``error_rate_hi``,
        ``overload_ratio_hi`` — defaults from :mod:`repro.core.adaptive`),
        or ``None`` entries for uncontrolled lanes.

    Budgets are precomputed once on the host — the EMA feedback trajectory
    depends only on the observation stream, not on routing — so every lane
    shares the same budget array and the sweep stays exact w.r.t. the
    single-lane jax backend (asserted by the grid-parity test).
    """
    spans = _LAST_RUN["spans"]
    cols = _as_columns(trace)
    n = len(cols)
    _LAST_RUN["n"] = n
    if n == 0:
        raise ValueError("run_fleet_grid needs a non-empty trace")

    # Budget-ordered pool frame, like FleetSim.
    ordered = sorted(pools.items(), key=lambda kv: kv[1][0].c_max)
    names = tuple(name for name, _ in ordered)
    base_inst = [int(ni) for _, (_, ni) in ordered]
    configs = [cfg for _, (cfg, _) in ordered]
    P = len(ordered)

    if thresholds is None:
        if set(names) == {"short", "long"}:
            base_th = [min(b_short, configs[0].c_max)]
        else:
            base_th = [c.c_max for c in configs[:-1]]
        thresholds = [base_th]
    if instances is None:
        instances = [base_inst]
    if gains is None:
        gains = [None]

    g = max(len(thresholds), len(instances), len(gains))
    thresholds = _broadcast_axis(list(thresholds), g, "thresholds")
    instances = _broadcast_axis(list(instances), g, "instances")
    gains = _broadcast_axis(list(gains), g, "gains")

    th_arr = np.asarray(thresholds, np.int32).reshape(g, P - 1)
    inst_arr = np.asarray(instances, np.int32).reshape(g, P)
    any_ctrl = any(gn is not None for gn in gains)
    ctrl_rows = []
    for gn in gains:
        row = {
            "enabled": np.int32(0 if gn is None else 1),
            "b_min": np.int32((gn or {}).get("b_min", 512)),
            "step": np.int32(
                (gn or {}).get("increase_step", DEFAULT_INCREASE_STEP)
            ),
            "factor": np.float32(
                (gn or {}).get("decrease_factor", DEFAULT_DECREASE_FACTOR)
            ),
            "err_hi": np.float32(
                (gn or {}).get("error_rate_hi", DEFAULT_ERROR_RATE_HI)
            ),
            "over_hi": np.float32(
                (gn or {}).get("overload_ratio_hi", DEFAULT_OVERLOAD_RATIO_HI)
            ),
        }
        ctrl_rows.append(row)
    ctrl = {
        k: np.stack([r[k] for r in ctrl_rows]) for k in ctrl_rows[0]
    }

    spec = _SimSpec(
        pools=tuple(
            _pool_spec(name, cfg, int(inst_arr[:, j].max()))
            for j, (name, cfg) in enumerate(zip(names, configs))
        ),
        w=float(timing.w_base),
        h=float(timing.h_per_seq),
        prefill_chunk=int(timing.prefill_chunk),
        win_size=int(control_window) if any_ctrl else 0,
    )

    budgets = None
    if P > 1:
        cal = calibrator or EmaCalibrator()
        epoch_cap = (
            max(1, min(epoch, control_window)) if any_ctrl else epoch
        )
        with _span("repro.sim.route_precompute", spans, n=n, g=g):
            budgets, _ = precompute_budget_trajectory(
                cols, cal, epoch_cap=epoch_cap
            )

    with jax.enable_x64():
        with _span("repro.sim.compile", spans, n=n, g=g):
            exe = _aot(spec, n, return_records, True, g)
        with _span("repro.sim.stage", spans, n=n, g=g):
            lane = {"th": th_arr, "ninst": inst_arr, "ctrl": ctrl}
            args = (_trace_arrays(cols, budgets), lane, _fresh_records(n, g))
        with _span("repro.sim.device_run", spans, n=n, g=g):
            out = jax.tree_util.tree_map(np.asarray, exe(*args))

    with _span("repro.sim.results", spans, n=n, g=g):
        _note_counters(out, spec, g)
        m = out["metrics"]
        return FleetGridResult(
            pool_names=names,
            thresholds=th_arr,
            instances=inst_arr,
            completed=m["completed"].astype(np.int64),
            rejected=m["rejected"].astype(np.int64),
            truncated=m["truncated"].astype(np.int64),
            preemptions=out["preempt"].sum(axis=1).astype(np.int64),
            routed=m["routed"].astype(np.int64),
            ttft_mean=m["ttft_mean"],
            ttft_p50=m["ttft_p50"],
            ttft_p99=m["ttft_p99"],
            tpot_mean=m["tpot_mean"],
            tpot_p99=m["tpot_p99"],
            makespan=m["makespan"],
            final_thresholds=out["th"].reshape(g, P - 1)[:, : P - 1],
            controller_moves=out["moves"].astype(np.int64),
            records=(
                _unpack_records(out["rec"], n) if "rec" in out else None
            ),
        )
