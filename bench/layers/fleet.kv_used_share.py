"""Mean share of the fleet's KV block budget in use (%).

Layer: the compiled event loop (``jax_engine._runner``). The counter
``kv_block_rounds`` sums the KV blocks in use after admission over the
real instances, over each lane's own rounds and over lanes;
``rounds_total`` sums the lanes' rounds, and ``real_kv_blocks`` is the
sum over pools of instances x the pool's per-instance budget. So this is
the mean over rounds of blocks in use over budget.

It says how near a cell runs to the eviction gate, which opens on a round
where some instance's block growth exceeds its free blocks: near 0, the
eviction and admission-reject paths are bypassed there. Like
``fleet.peak_live_share`` it is a control counter, fixed by the traffic
and the simulated semantics: a change of speed must read it identically
at parent and change. Read from ``jax_engine.last_run_stats()`` of the
window's last call. A program without the counter gives nothing. Moves
``sim_lane_req_per_s``."""


def read(ctx):
    from repro.sim import jax_engine

    s = jax_engine.last_run_stats()
    if ("kv_block_rounds" not in s or not s.get("rounds_total")
            or not s.get("real_kv_blocks")):
        return None
    return 100.0 * s["kv_block_rounds"] / (
        s["rounds_total"] * s["real_kv_blocks"])
