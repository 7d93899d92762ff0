"""Without a TPU the harness runs nothing, prints no result, exits non-zero."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from bench.tests.tiny import REPO


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "ALLOW_MULTIPLE_LIBTPU_LOAD")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fleet-azure-1k.single",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_exits_nonzero_on_the_cpu():
    p = _run(REPO)
    assert p.returncode != 0
    assert "platform=cpu" in p.stdout
    assert _no_result(p.stdout)


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
