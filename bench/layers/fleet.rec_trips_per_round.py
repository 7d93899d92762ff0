"""Record-write trips the device executed per executed round.

Layer: the compiled event loop (``jax_engine._runner``, the trip loop
under the ``record_scatter`` scope: each trip writes the records of up
to 32 completing slots, and a round with no completion runs none).
Read from ``jax_engine.last_run_stats()`` of the window's last call;
under vmap a round's trips run until the lane with the most completions
is done. A program without the counter gives nothing. Moves
``sim_lane_req_per_s``."""


def read(ctx):
    from repro.sim import jax_engine

    s = jax_engine.last_run_stats()
    if "rec_trips" not in s or not s.get("rounds"):
        return None
    return s["rec_trips"] / s["rounds"]
