"""Admission-fixpoint waves the device executed per executed round.

Layer: the compiled event loop (``jax_engine._runner``, the inner
``while`` under the ``admit`` scope). Read from
``jax_engine.last_run_stats()`` of the window's last call; under vmap a
wave runs until the slowest lane's admission is done. A program without
the counter gives nothing. Moves ``sim_lane_req_per_s``."""


def read(ctx):
    from repro.sim import jax_engine

    s = jax_engine.last_run_stats()
    if "adm_waves" not in s or not s.get("rounds"):
        return None
    return s["adm_waves"] / s["rounds"]
