"""Sweep rounds the device executed per request of a lane.

Layer: the compiled event loop (``jax_engine._runner``). Read from
``jax_engine.last_run_stats()``: under vmap the lanes run in lockstep, so
the device executes the lane maximum. Moves ``sim_lane_req_per_s``."""


def read(ctx):
    c = ctx.counters
    if not c.get("n"):
        return None
    return c["rounds"] / c["n"]
