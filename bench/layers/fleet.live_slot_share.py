"""Share of the slot rows each executed round works on that hold a live
request (%).

Layer: the compiled event loop, whose rounds work over the padded
``(P, max I, max S)`` slot state of every lane. Live decode slots after
admission, summed over each lane's own rounds and over lanes, over
lanes x executed rounds x slot rows; a lane that has finished but still
runs masked rounds adds to the bottom only, so padding and lockstep
waste show in one number. Read from ``jax_engine.last_run_stats()`` of
the window's last call. A program without the counter gives nothing.
Moves ``sim_lane_req_per_s``."""


def read(ctx):
    from repro.sim import jax_engine

    s = jax_engine.last_run_stats()
    if "live_slot_rounds" not in s or not s.get("rounds"):
        return None
    return 100.0 * s["live_slot_rounds"] / (
        s["g"] * s["rounds"] * s["slot_rows"])
