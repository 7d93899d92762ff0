"""Share of the traced sweep in which the device ran no executable (%).

Layer: the fleet entry on the host (``run_fleet_grid`` / ``FleetSim.run``:
routing precompute, argument transfer, record back-fill). Moves
``sim_lane_req_per_s``."""


def read(ctx):
    if ctx.trace is None or ctx.trace.devices == 0:
        return None
    return 100.0 * ctx.trace.idle_share
