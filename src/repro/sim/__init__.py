"""Discrete-event simulator for fleet sizing / latency / reliability
(paper Appendix A: instance DES, analytical profiler, fleet verification).

Three interchangeable fleet backends (``FleetSim(backend=...)``):

* ``"reference"`` — scalar engine (:mod:`repro.sim.engine`): one Python
  object per sequence; ground truth for unit tests.
* ``"vectorized"`` — struct-of-arrays engine
  (:mod:`repro.sim.vector_engine`): all instances of a pool step together
  in masked NumPy ops with event-distance jumps, epoch-batched N-way JAX
  routing and EMA sync, consuming traces natively as
  :class:`~repro.traces.generator.TraceColumns`; 10×+ faster at fleet
  scale (``benchmarks/sim_throughput.py``) and behaviourally equivalent
  (``tests/test_vector_engine.py``).
* ``"jax"`` — fully compiled engine (:mod:`repro.sim.jax_engine`): the
  whole event loop as a jitted ``lax.while_loop`` over fixed-shape slot
  arrays, bit-identical to the host backends in the exact classes on
  the CPU (on a TPU, emulated float64 can move the last bits).
  Its batched sweep API :func:`run_fleet_grid` ``vmap``\\ s entire fleet
  simulations across threshold / instance-count / controller-gain axes —
  5×+ faster than the serial vectorized loop on ≥16-point sensitivity
  grids once the one-off XLA compile is amortized. Prefer ``vectorized``
  for one-off runs with faults / spillover / event tracing; prefer
  ``jax`` for grids and controller tuning.

Fleets route over a budget-ordered :class:`~repro.core.pools.PoolSet` —
any pool count, the paper's short/long pair being P=2.

Fault injection (:mod:`repro.sim.faults`): pass
``FleetSim(..., injector=FaultInjector(specs), retry_policy=RetryPolicy())``
to subject either backend to instance crashes, KV-OOM kills, and transient
slowdowns with retry/timeout/backoff and health-gated routing. Both
backends implement identical fault semantics; fault-off runs are
bit-identical to pre-fault builds.
"""

from repro.sim.engine import InstanceSim
from repro.sim.faults import FaultInjector, FaultRuntime, FaultSpec, RetryPolicy
from repro.sim.fleet import FleetResult, FleetSim, PoolSim, run_fleet
from repro.sim.jax_engine import FleetGridResult, run_fleet_grid
from repro.sim.metrics import (
    PAPER_SLO,
    RequestRecord,
    SimSummary,
    SLOTarget,
    concat_record_columns,
    percentile,
    summarize,
    summarize_columns,
)
from repro.sim.vector_engine import VectorPoolSim
from repro.sim.profiler import (
    HEADROOM,
    FleetPlan,
    PoolProfile,
    mean_iterations,
    plan_fleet,
    profile_pool,
    sensitivity_sweep,
    split_by_budget,
)
from repro.sim.timing import (
    A100_LLAMA3_70B,
    MI300X_QWEN3,
    TimingModel,
    tpu_v5e_model,
)

__all__ = [
    "InstanceSim",
    "FaultInjector",
    "FaultRuntime",
    "FaultSpec",
    "RetryPolicy",
    "FleetResult",
    "FleetSim",
    "PoolSim",
    "run_fleet",
    "FleetGridResult",
    "run_fleet_grid",
    "RequestRecord",
    "SimSummary",
    "SLOTarget",
    "PAPER_SLO",
    "concat_record_columns",
    "percentile",
    "summarize",
    "summarize_columns",
    "VectorPoolSim",
    "HEADROOM",
    "FleetPlan",
    "PoolProfile",
    "mean_iterations",
    "plan_fleet",
    "profile_pool",
    "sensitivity_sweep",
    "split_by_budget",
    "A100_LLAMA3_70B",
    "MI300X_QWEN3",
    "TimingModel",
    "tpu_v5e_model",
]
