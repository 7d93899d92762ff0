"""A copy of the benchmark at tiny sizes, for driving whole runs on the CPU."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def _edit(path: Path, fn) -> None:
    data = json.loads(path.read_text())
    fn(data)
    path.write_text(json.dumps(data))


def make(dst: Path, *, logit_gap: float = 0.2) -> Path:
    """``dst`` holds BENCHMARK.json and bench/ with tiny configurations:
    a fleet of 3 short and 8 long instances over 300 requests, and a
    2-layer, 128-wide decoder behind pools of 256 x 4 and 2048 x 2."""
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    cfg = dst / "bench" / "configs"

    def fleet(c):
        c["pools"][0]["instances"], c["pools"][1]["instances"] = 3, 8
        c["trace_requests"] = 300

    def serve(c):
        c["model"].update(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                          head_dim=32, d_ff=256, vocab=512)
        c["pools"] = [
            {"name": "short", "c_max": 256, "slots": 4, "prompt_bucket": 64},
            {"name": "long", "c_max": 2048, "slots": 2, "prompt_bucket": 256},
        ]
        c["server"]["b_short"] = 256
        c["trace"] = {"start_frac": 0.3, "seconds": 0.5}
        c["checks"]["logit_gap"] = logit_gap

    _edit(cfg / "fleet-azure-1k.json", fleet)
    _edit(cfg / "yi-6b-8l.json", serve)
    _edit(dst / "bench" / "traffic" / "chat.json",
          lambda t: t.update(rate=2.0))
    _edit(dst / "bench" / "traffic" / "single.json",
          lambda t: t.update(requests=300))
    return dst
