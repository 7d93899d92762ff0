"""Median host time of one ``TwoPoolServer.submit`` call (us).

Layer: router and server (route, calibrate, enqueue). Read from the
harness's ``bench.submit`` spans in the trace. Moves ``ttft_p95_ms``."""

import statistics


def read(ctx):
    if ctx.trace is None:
        return None
    d = ctx.trace.spans.get("bench.submit")
    return 1e6 * statistics.median(d) if d else None
