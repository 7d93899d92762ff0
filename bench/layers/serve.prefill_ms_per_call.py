"""Device time of one prefill call (ms).

Layer: engine prefill (``ServingEngine._admit`` -> the jitted
``model.prefill``, executable ``jit_prefill``), over the traced window.
Moves ``ttft_p95_ms``."""


def read(ctx):
    if ctx.trace is None or ctx.trace.devices == 0:
        return None
    sec, runs = ctx.trace.module_time(r"(^|_)prefill$")
    return 1e3 * sec / runs if runs else None
