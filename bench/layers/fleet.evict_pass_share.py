"""Share of the executed rounds in which the eviction pass ran (%).

Layer: the compiled event loop (the pass over the ``(P, I, S, S)`` slot
square under the ``evict`` scope: every round on a vmapped grid, the
gate's taken branch on a single lane). Read from
``jax_engine.last_run_stats()`` of the window's last call. A program
without the counter gives nothing. Moves ``sim_lane_req_per_s``."""


def read(ctx):
    from repro.sim import jax_engine

    s = jax_engine.last_run_stats()
    if "evict_runs" not in s or not s.get("rounds"):
        return None
    return 100.0 * s["evict_runs"] / s["rounds"]
