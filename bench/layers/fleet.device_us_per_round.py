"""Device time of the event-loop executable per executed round (us).

Layer: the compiled event loop. The loop executable is the one named
``core`` (``jax.jit`` of the simulation core); its device time in the
traced calls is divided by the rounds those calls executed. Moves
``sim_lane_req_per_s``."""


def read(ctx):
    c = ctx.counters
    if ctx.trace is None or not c.get("traced_calls"):
        return None
    sec, runs = ctx.trace.module_time(r"(^|_)core$")
    if runs == 0:
        return None
    return 1e6 * sec / (c["traced_calls"] * c["rounds"])
