"""The fleet cells' loop-counter and host-span metrics, in a whole traced run.

A tiny copy of the benchmark (fewer requests a call), driven through
``execute`` with the look for a chip skipped: each metric that reads the
program's counters and spans is reported, as a finite number.
"""

from __future__ import annotations

import json
import math

import pytest

from bench.tests import tiny

METRICS = ("fleet.adm_waves_per_round", "fleet.evict_pass_share",
           "fleet.live_slot_share", "fleet.host_ms_per_call")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make(tmp_path_factory.mktemp("bench"))
    for path, key in ((root / "bench" / "configs" / "fleet-azure-1k.json",
                       "trace_requests"),
                      (root / "bench" / "traffic" / "single.json",
                       "requests")):
        data = json.loads(path.read_text())
        data[key] = 120
        path.write_text(json.dumps(data))
    return root


@pytest.mark.parametrize("workload", ["fleet-azure-1k.sweep16",
                                      "fleet-azure-1k.single"])
def test_loop_metrics_reported(root, workload):
    from bench.run import execute

    result = execute(workload, 2**33 + 5, 1.0, True, root=str(root),
                     require_tpu=False)
    assert result["correct"] is True
    metrics = result["metrics"]
    for name in METRICS:
        value = metrics[name]["value"]
        assert isinstance(value, float) and math.isfinite(value), name
        assert value >= 0.0, name
    assert 0.0 < metrics["fleet.live_slot_share"]["value"] <= 100.0
    if workload.endswith("sweep16"):
        assert metrics["fleet.evict_pass_share"]["value"] == 100.0
    else:  # no trace of this mix preempts
        assert metrics["fleet.evict_pass_share"]["value"] == 0.0
