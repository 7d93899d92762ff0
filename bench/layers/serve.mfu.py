"""Useful model FLOPs over the traced steps' wall time at the chip's peak (%).

Layer: the model step (``models/transformer.py``), whole. Useful FLOPs are
those of the real prompt tokens prefilled and the tokens decoded in the
traced ``TwoPoolServer.step`` calls, attention over each token's real
context and no padding (``bench/flops.py``); the time is the sum of those
calls' ``bench.step`` spans. Moves ``tpot_p95_ms``."""


def read(ctx):
    c = ctx.counters
    if ctx.trace is None or ctx.trace.devices == 0:
        return None
    wall = sum(ctx.trace.spans.get("bench.step", []))
    useful = c.get("prefill_flops", 0) + c.get("decode_flops", 0)
    if wall <= 0 or useful <= 0:
        return None
    return 100.0 * useful / (wall * ctx.peaks["bf16_flops"])
