"""Host time of one fleet call outside the device run (ms).

Layer: the fleet entry on the host (``run_fleet_grid`` /
``FleetSim.run`` -> ``run_fleet_jax``): routing precompute, compile-cache
lookup, staging, and result assembly. The program's span
``repro.sim.run`` less its child ``repro.sim.device_run``, from
``jax_engine.last_run_stats()`` of the window's last call. A program
without the spans gives nothing. Moves ``sim_lane_req_per_s``."""


def read(ctx):
    from repro.sim import jax_engine

    spans = jax_engine.last_run_stats().get("spans", {})
    if "repro.sim.run" not in spans or "repro.sim.device_run" not in spans:
        return None
    return 1e3 * (spans["repro.sim.run"] - spans["repro.sim.device_run"])
