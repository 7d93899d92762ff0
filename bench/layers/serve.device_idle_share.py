"""Share of the traced serving window in which the device ran nothing (%).

Layer: the device. Idle gaps are named in the result's ``breakdown`` by
the host span they fall in (``bench.step`` host work, ``bench.submit``,
or waiting for arrivals outside any span). Moves ``tpot_p95_ms``."""


def read(ctx):
    if ctx.trace is None or ctx.trace.devices == 0:
        return None
    return 100.0 * ctx.trace.idle_share
