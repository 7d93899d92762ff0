"""The phases of ``chip_smoke.py`` at tiny size on the CPU.

The script itself refuses to run without a TPU; these tests call its
phase functions directly so that their checks are exercised on every
test run.
"""

import importlib.util
from pathlib import Path

import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert "platform 'cpu'" in out.err
    assert '"ok"' not in out.out


def test_fleet_phase_tiny(smoke):
    out = smoke.fleet_phase(requests=200, lanes=4, exact_requests=200)
    # on the CPU the compiled tier is bit-identical in the exact class
    assert out["exact"]["bit_identical"]
    assert out["run_stats"]["mode"] == "fleet"
    assert out["grid_stats"]["g"] == 4


def test_serve_phase_tiny(smoke):
    out = smoke.serve_phase(reduced=True, short=(128, 4), long=(512, 2),
                            n_short=1, n_long=1)
    assert out["by_pool"] == {"short": 1, "long": 1}
    assert sum(out["calibration_counts"]) == 2
    assert out["forward_check"]["exact"] == 4


def test_serve_workload_splits_pools(smoke):
    work = smoke.serve_workload(256_000, 2048, 12, 4, seed=0)
    assert len(work) == 16
    longs = [w for w in work if w[2] == 2048 // 8]
    assert len(longs) == 4
    # each group of prompts shares one 64-token prefill bucket
    assert {-(-len(w[0]) // 64) for w in longs} == {2048 // 64 - 1}
    shorts = [w for w in work if w[2] == 8]
    assert {-(-len(w[0]) // 64) for w in shorts} == {2}
