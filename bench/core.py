"""The harness's machinery: discovery by name, the window's clock, tracing.

Everything that belongs to one cell part lives in a file of its own and is
found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``  a configuration (sizes, constants);
* ``bench/traffic/<mix>.json``     a traffic mix; it names its ``driver``;
* ``bench/drivers/<driver>.py``    one driver per entry point of the
  program, exposing ``Driver(ctx)``;
* ``bench/layers/<metric>.py``     one reader per per-layer metric,
  exposing ``read(ctx)``, which returns a number or ``None``.

Adding a cell, configuration, mix or metric adds files and entries; no
file here changes.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

#: Events JAX records each time it produces an executable, compiled or
#: loaded from the persistent cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_module(path: Path, name: str):
    """Import one plugin file by path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_plugin_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    driver: Any  # the driver module
    end_to_end: list  # metric entries this cell reports
    per_layer: list
    readers: dict  # per-layer metric name -> reader module


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def with_held_out(bench: dict, bench_dir: Path = BENCH_DIR) -> dict:
    """``bench`` with the entries of every ``bench/held_out/*.json`` added.

    A held-out file holds the ``BENCHMARK.json`` entries of a cell that is
    not yet admitted (its limits or its load still want chip readings), so
    that the harness and its tools can run it by name; the benchmark's own
    runs never name it."""
    merged = {k: list(v) if isinstance(v, list) else v
              for k, v in bench.items()}
    for path in sorted((bench_dir / "held_out").glob("*.json")):
        for key, entries in json.loads(path.read_text()).items():
            have = {e["name"] for e in merged[key]}
            merged[key] += [e for e in entries if e["name"] not in have]
    return merged


def resolve(bench: dict, workload: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """Find the workload's configuration, mix, driver and metric readers,
    in ``BENCHMARK.json`` or, failing that, among the held-out cells."""
    bench = with_held_out(bench, bench_dir)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"unknown workload {workload!r}; have {sorted(by_name)}")
    w = by_name[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((bench_dir.parent / conf["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    driver = load_module(bench_dir / "drivers" / f"{traffic['driver']}.py",
                         traffic["driver"])
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [])
             or ("workloads" not in m and m["moves"] in reported)]
    readers = {m["name"]: load_module(bench_dir / "layers" / f"{m['name']}.py",
                                      m["name"]) for m in layer}
    return Cell(workload, int(w["chips"]), config, traffic, driver, e2e,
                layer, readers)


class CompileCounter:
    """Counts executables JAX produces while ``active`` is set."""

    def __init__(self) -> None:
        self.active = False
        self.count = 0

    def install(self) -> None:
        import jax

        def listener(event: str, duration: float, **_: Any) -> None:
            if self.active and event == COMPILE_EVENT:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


class Tracer:
    """Host spans and the traced part of the window.

    ``span(name)`` marks a call into a layer of the program on the
    profiler's clock. Drivers call ``tick(elapsed)`` between units of work;
    with tracing on, the profiler starts at the first tick at or after
    ``start_s`` into the window and stops at the first tick at or after
    ``start_s + length_s`` (so whole units are traced)."""

    def __init__(self, enabled: bool, start_s: float = 0.0,
                 length_s: float = 0.0) -> None:
        self.enabled = enabled
        self.start_s, self.length_s = start_s, length_s
        self.dir: Optional[str] = None
        self.state = "idle"  # idle -> tracing -> done
        self.t_start = 0.0

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def tick(self, elapsed: float) -> None:
        if not self.enabled:
            return
        import jax

        if self.state == "idle" and elapsed >= self.start_s:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir)
            self.state, self.t_start = "tracing", time.perf_counter()
        elif (self.state == "tracing"
              and elapsed >= self.start_s + self.length_s
              and time.perf_counter() > self.t_start):
            self.stop()

    def stop(self) -> None:
        if self.state == "tracing":
            import jax

            jax.profiler.stop_trace()
            self.state = "done"

    def xplane(self) -> Optional[str]:
        if self.dir is None:
            return None
        found = sorted(Path(self.dir).glob("plugins/profile/*/*.xplane.pb"))
        return str(found[-1]) if found else None

    def cleanup(self) -> None:
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit (pass: ``<=``)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Context:
    """What a driver and the layer readers see."""

    cell: Cell
    seed: int
    seconds: float
    tracer: Tracer
    log: Callable[[str], None]
    peaks: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    trace: Any = None  # trace_reduce.Reduced, on a traced run

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


def load_peaks(kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def jax_seed(seed: int) -> int:
    """A 31-bit key seed from any whole number (the driver's run large)."""
    digest = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF
