"""Cells, configurations, mixes and metrics are found by name."""

from __future__ import annotations

import json
import re

import pytest

from bench import core
from bench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_of_the_benchmark_resolves():
    bench = core.load_benchmark()
    for w in bench["workloads"]:
        cell = core.resolve(bench, w["name"])
        assert callable(getattr(cell.driver, "Driver"))
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in reported
            assert callable(getattr(cell.readers[m["name"]], "read"))


def test_held_out_cells_resolve_by_name_only():
    """A held-out cell runs by name but is no cell of ``BENCHMARK.json``."""
    bench = core.load_benchmark()
    admitted = {w["name"] for w in bench["workloads"]}
    held = core.with_held_out(bench)
    for w in held["workloads"]:
        if w["name"] in admitted:
            continue
        cell = core.resolve(bench, w["name"])
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert cell.per_layer
    assert {w["name"] for w in core.load_benchmark()["workloads"]} == admitted


def test_benchmark_file_shape():
    bench = core.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in bench["configs"]:
        data = json.loads((core.ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(data["reduced"])


def test_new_files_alone_add_a_cell(tmp_path):
    """A dummy configuration, mix, driver and metric, added as files and
    entries, are found without editing any file of the harness."""
    root = tiny.make(tmp_path)
    bench_dir = root / "bench"
    (bench_dir / "configs" / "dummy.json").write_text('{"answer": 42}')
    (bench_dir / "traffic" / "ping.json").write_text(
        '{"driver": "echo", "generator": "none"}')
    (bench_dir / "drivers" / "echo.py").write_text(
        "class Driver:\n"
        "    trace_op_line = 'XLA Ops'\n"
        "    def __init__(self, ctx):\n"
        "        self.ctx = ctx\n")
    (bench_dir / "layers" / "echo.answer.py").write_text(
        "def read(ctx):\n    return ctx.config['answer']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dummy", "source": "x",
                             "file": "bench/configs/dummy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy.ping", "config": "dummy",
                               "traffic": "ping", "chips": 1, "why": "t"})
    bench["end_to_end"].append({"name": "pings", "unit": "1/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["dummy.ping"]})
    bench["per_layer"].append({"name": "echo.answer", "unit": "1",
                               "better": "higher", "source": "program_counter",
                               "layer": "echo", "moves": "pings",
                               "workloads": ["dummy.ping"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = core.resolve(core.load_benchmark(root), "dummy.ping", bench_dir)
    assert cell.config == {"answer": 42}
    assert {m["name"] for m in cell.end_to_end} == {"pings", "setup_s"}
    assert list(cell.readers) == ["echo.answer"]
    assert cell.readers["echo.answer"].read(
        core.Context(cell, 1, 1.0, core.Tracer(False), print)) == 42
    with pytest.raises(KeyError):
        core.resolve(bench, "no.such.cell", bench_dir)
