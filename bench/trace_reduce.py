"""Reduce a profiler trace (``.xplane.pb``) to the numbers the readers use.

Device planes are those named ``/device:TPU:<i>``. On each, the events of
the ``XLA Modules`` line are whole executables and those of ``XLA Ops``
single operations (a plane that has only one of the two lines uses it for
both). Host spans are the harness's ``jax.profiler.TraceAnnotation``
events, on any line of the ``/host:CPU`` plane, named ``bench.*``.

* busy: the union of the operation intervals of a device, inside the
  traced window, averaged over the devices used;
* per executable: summed device time and number of runs, by module name
  with its ``(<id>)`` suffix removed;
* idle gaps: the complement of busy within the window, each named by the
  innermost host span that covers its midpoint.
"""

from __future__ import annotations

import collections
import dataclasses
import re

SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Reduced:
    window_s: float  # length of the traced window
    busy_s: float  # device busy seconds, averaged over devices
    devices: int
    modules: dict  # name -> [seconds, runs], summed over devices
    ops: dict  # op name -> seconds, summed over devices
    spans: dict  # span name -> list of durations (s)
    gaps: list  # (seconds, span name) idle gaps, longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0

    def module_time(self, pattern: str) -> tuple[float, int]:
        """(device seconds per device, runs per device) of the executables
        whose name matches ``pattern`` (a regular expression)."""
        rx = re.compile(pattern)
        sec = sum(v[0] for k, v in self.modules.items() if rx.search(k))
        runs = sum(v[1] for k, v in self.modules.items() if rx.search(k))
        d = max(1, self.devices)
        return sec / d, runs // d


def union_length(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def idle_gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Maximal sub-intervals of [lo, hi] covered by no interval."""
    gaps, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            gaps.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        gaps.append((cur, hi))
    return [(a, b) for a, b in gaps if b > a]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def reduce_events(device_ops: list[list[tuple[float, float, str]]],
                  device_modules: list[list[tuple[float, float, str]]],
                  spans: list[tuple[float, float, str]]) -> Reduced:
    """The reduction on plain ``(start_s, end_s, name)`` events.

    The window is the extent of the host spans when there are any (the
    harness opens one span per traced unit of work), else of the device
    events."""
    every = [e for dev in device_ops for e in dev] + spans
    if not every:
        raise ValueError("the trace holds no events")
    if spans:
        lo, hi = min(s[0] for s in spans), max(s[1] for s in spans)
    else:
        lo, hi = min(e[0] for e in every), max(e[1] for e in every)
    window = hi - lo
    busy_total, all_gaps = 0.0, []
    modules: dict = collections.defaultdict(lambda: [0.0, 0])
    ops: dict = collections.defaultdict(float)
    for dev_ops, dev_mods in zip(device_ops, device_modules):
        iv = _clip([(a, b) for a, b, _ in dev_ops], lo, hi)
        busy_total += union_length(iv)
        all_gaps.extend(idle_gaps(iv, lo, hi))
        for a, b, name in dev_ops:
            ops[name] += b - a
        for a, b, name in dev_mods:
            m = modules[_SUFFIX.sub("", name)]
            m[0] += b - a
            m[1] += 1
    n_dev = max(1, len(device_ops))
    by_span: dict = collections.defaultdict(list)
    for a, b, name in spans:
        by_span[name].append(b - a)

    def cover(t: float) -> str:
        inner = [(b - a, name) for a, b, name in spans if a <= t <= b]
        return min(inner)[1] if inner else "outside any span"

    gaps = sorted(((b - a, cover((a + b) / 2)) for a, b in all_gaps),
                  reverse=True)
    return Reduced(window, busy_total / n_dev, len(device_ops), dict(modules),
                   dict(ops), dict(by_span), gaps)


def _events(line) -> list[tuple[float, float, str]]:
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in line.events]


def reduce_xplane(path: str, op_line: str = "XLA Ops",
                  device_prefix: str = "/device:TPU:") -> Reduced:
    """Read ``path`` with ``jax.profiler.ProfileData`` and reduce it.

    ``op_line`` names the device line whose events count as busy time:
    ``XLA Modules`` where one executable runs a long device loop, so that
    no per-iteration operation events are needed."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev_ops, dev_mods, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get(op_line) or lines.get("XLA Modules")
            mods = lines.get("XLA Modules") or lines.get("XLA Ops")
            if ops is None:
                continue
            dev_ops.append(_events(ops))
            dev_mods.append(_events(mods))
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(e for e in _events(ln)
                             if e[2].startswith(SPAN_PREFIX))
    return reduce_events(dev_ops, dev_mods, spans)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The ``breakdown`` of a traced result line."""
    ops = sorted(red.ops.items(), key=lambda kv: -kv[1])[:top]
    per_dev = max(1, red.devices)
    return {
        "device_ops": [[name, sec / per_dev] for name, sec in ops],
        "idle_gaps": [[name, sec] for sec, name in red.gaps[:top]],
    }
