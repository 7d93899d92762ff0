"""simlint tolerance manifest: the three-tier equivalence contract.

This module is the machine-readable form of the contract the runtime
equivalence suites (``tests/test_vector_engine.py``) check empirically:
which counters/events/result fields every DES backend must produce, and
which divergences of the compiled ``jax`` tier are *intentional* and
bounded by their own tests rather than bugs.

Every allowance carries a reason string.  Adding an entry here is a
reviewed statement "this divergence is by design"; prefer it over inline
``# simlint: disable=`` comments for anything that is part of the tier
contract (inline suppressions are for one-off local exceptions).

``python -m repro.analysis --manifest`` dumps this as JSON.
"""

from __future__ import annotations

import copy
import json

SCHEMA = "repro.simlint/manifest-v1"

DEFAULT_MANIFEST: dict = {
    "schema": SCHEMA,
    # ------------------------------------------------------------------
    # The three interchangeable DES backends (suffix-matched on path).
    # ------------------------------------------------------------------
    "engines": {
        "reference": "repro/sim/engine.py",
        "vectorized": "repro/sim/vector_engine.py",
        "jax": "repro/sim/jax_engine.py",
    },
    # ------------------------------------------------------------------
    # engine-parity: counters.  Canonical counter -> the symbol each
    # engine must write.  Host engines increment `self.<symbol>`; the
    # jax tier carries them as dict keys inside the jitted while_loop.
    # ------------------------------------------------------------------
    "counters": {
        "preemption_count": {
            "reference": "preemption_count",
            "vectorized": "preemption_count",
            "jax": "npre",
        },
        "rejection_count": {
            "reference": "rejection_count",
            "vectorized": "rejection_count",
            "jax": "nrej",
        },
        "truncation_count": {
            "reference": "truncation_count",
            "vectorized": "truncation_count",
            "jax": "ntr",
        },
    },
    # ------------------------------------------------------------------
    # engine-parity: event kinds each engine emits on its hot path.
    # The jax tier cannot emit discrete events from inside a jitted
    # lax.while_loop; FleetSim(backend="jax") rejects event tracing up
    # front, so the whole canonical set is declared missing-by-design.
    # ------------------------------------------------------------------
    "events": {
        "canonical": ["admit", "preempt", "truncate", "reject"],
        "missing_ok": {
            "jax": {
                "admit": "no per-event callbacks inside jit; "
                "FleetSim raises if events are requested on the jax tier",
                "preempt": "counted in the carried npre counter instead",
                "truncate": "counted in the carried ntr counter instead",
                "reject": "counted in the carried nrej counter instead",
            }
        },
    },
    # ------------------------------------------------------------------
    # engine-parity: FleetResult construction.  The reference
    # constructor is the canonical field set; other tiers may omit only
    # what is declared here.
    # ------------------------------------------------------------------
    "fleet_result": {
        "constructors": {
            "reference": {"file": "repro/sim/fleet.py", "function": "_run_reference"},
            "vectorized": {"file": "repro/sim/fleet.py", "function": "_run_vectorized"},
            "jax": {"file": "repro/sim/jax_engine.py", "function": "run_fleet_jax"},
        },
        "missing_ok": {
            "vectorized": {
                "records": "outcomes stay columnar (summarize_columns); "
                "per-request Record objects are a reference-tier feature",
            },
            "jax": {
                "retries": "fault injection unsupported inside the jitted loop",
                "timeouts": "fault injection unsupported inside the jitted loop",
                "shed": "fault injection unsupported inside the jitted loop",
                "instance_failures": "fault injection unsupported inside "
                "the jitted loop",
                "availability": "defaults to 1.0; no fault runtime on this tier",
                "records": "fixed-shape slot arrays, no Record objects",
                "fail_records": "no fault runtime on this tier",
            },
        },
    },
    # ------------------------------------------------------------------
    # dtype-discipline: float64 op-order contract for DES time math.
    # Scoped to the compiled engine plus the one device kernel that IS
    # event-time math (repro/kernels/sim_decode.py — its decode-advance
    # pass must accumulate the engines' float64 event times bit for bit).
    # Other device kernels pick compute precision explicitly per
    # accelerator (f32/bf16 accumulators) and stay outside the contract.
    # ------------------------------------------------------------------
    "dtype": {
        "files": [
            "repro/sim/jax_engine.py",
            "repro/kernels/sim_decode.py",
        ],
        "float32_scope_ok": {
            "repro/sim/jax_engine.py": {
                "window_step": "in-step AIMD controller mirror keeps gains "
                "and pressure ratios in float32 for vmappable lane axes; "
                "decisions are threshold comparisons, bounded by the "
                "gain-grid parity tests",
                "_ctrl_params": "the one table of controller lane fields "
                "(both entry points' gain rows and the AOT abstract inputs "
                "read it) mirrors window_step's float32 lanes",
                "precompute_budget_trajectory": "EMA calibration state is "
                "float32 by the CalibState contract (core/calibration.py); "
                "the output is int32 budgets, never event-time math — "
                "cold-start parity tests bound it",
            }
        },
        "const_attrs": ["w_base", "h_per_seq"],
        "const_wrappers": ["float", "np.float64", "jnp.float64"],
        "x64_entries": {
            "repro/sim/jax_engine.py": ["_runner", "_aot"],
        },
        "kernels_note": "repro/kernels/* excluded except sim_decode.py: "
        "the Pallas compute kernels (attention, scan) choose their own "
        "compute precision, but sim_decode advances DES event times and "
        "must hold the same float64 op-order contract as the engines; "
        "event-time constants flow through timing.constants_f64()",
    },
    # ------------------------------------------------------------------
    # jit-purity: extra jit roots not discoverable syntactically
    # (none today — jax.jit/vmap/lax.* call sites are found by name).
    # ------------------------------------------------------------------
    "jit": {"extra_roots": {}},
    # ------------------------------------------------------------------
    # event-schema: obs wiring.  Telemetry column families the producer
    # emits that the validator intentionally does not require.
    # ------------------------------------------------------------------
    "telemetry": {
        "events_file": "repro/obs/events.py",
        "validate_file": "repro/obs/validate.py",
        "timeseries_file": "repro/obs/timeseries.py",
        "emitter_files": [
            "repro/sim/engine.py",
            "repro/sim/vector_engine.py",
            "repro/sim/fleet.py",
            "repro/sim/faults.py",
            "repro/obs/timeseries.py",
        ],
        "unvalidated_families_ok": {
            "threshold": "per-boundary count varies with pool count P; "
            "optional trajectory family",
            "calib_err": "per-category diagnostics; category count is "
            "config-dependent",
            "ema_ratio": "per-category diagnostics; category count is "
            "config-dependent",
        },
    },
}


def manifest_dict() -> dict:
    """Deep copy of the default tolerance manifest."""
    return copy.deepcopy(DEFAULT_MANIFEST)


def manifest_json(indent: int = 2) -> str:
    return json.dumps(DEFAULT_MANIFEST, indent=indent, sort_keys=False)
