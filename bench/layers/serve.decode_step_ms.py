"""Device time of one slot-decode call (ms).

Layer: engine decode (the vmapped one-token decode over every slot,
executable ``jit_single``), over the traced window. Moves ``tpot_p95_ms``."""


def read(ctx):
    if ctx.trace is None or ctx.trace.devices == 0:
        return None
    sec, runs = ctx.trace.module_time(r"(^|_)single$")
    return 1e3 * sec / runs if runs else None
