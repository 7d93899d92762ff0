"""Slot decode's share of its roofline (%).

Layer: the kernels of the slot-decode program, taken as one unit until
the program names its scopes. The least time of the traced decode calls
is the larger of their useful FLOPs over the bf16 peak and their needed
bytes over the HBM bandwidth (``bench/flops.py``: weights once, the keys
and values of live tokens only); the share is that over the measured
device time of ``jit_single``. Decode is bound by HBM. Moves
``tpot_p95_ms``."""


def read(ctx):
    c = ctx.counters
    if ctx.trace is None or ctx.trace.devices == 0 or not c.get("decode_calls"):
        return None
    sec, runs = ctx.trace.module_time(r"(^|_)single$")
    if sec <= 0:
        return None
    least = max(c["decode_flops"] / ctx.peaks["bf16_flops"],
                c["decode_bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / sec
