"""Most live slots in any round, as a share of the fleet's real slots (%).

Layer: the compiled event loop (``jax_engine._runner``). The counter
``live_peak`` is the most live decode slots after admission in any
executed round, summed over the fleet (a grid's lane maximum), and
``real_slot_rows`` is the sum over pools of instances x ``n_seq``, with
no padding. A round sums instances that stand at their own clocks, so
this can read a few requests below the most live at one instant.

This is a descriptor of the cell, not a target: it is fixed by the
traffic and the simulated semantics, so a change of speed that keeps the
rounds must read it identically at parent and change, and a difference
means the semantics moved. Its entry's ``better`` and ``moves`` fields
are required by the benchmark file's form and carry no claim; it says
how near its operating point each cell's fleet ran, and so how much of
the slot state a round's work is about. Read from
``jax_engine.last_run_stats()`` of the window's last call. A program
without the counter gives nothing."""


def read(ctx):
    from repro.sim import jax_engine

    s = jax_engine.last_run_stats()
    if "live_peak" not in s or not s.get("real_slot_rows"):
        return None
    return 100.0 * s["live_peak"] / s["real_slot_rows"]
