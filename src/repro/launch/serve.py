"""Two-pool serving driver (the paper's system, runnable end to end).

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --requests 40
    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --full-width \
        --short-cmax 2048 --long-cmax 8192

Builds a model (the reduced preset by default, the published widths with
``--full-width``), a short pool and a long pool (right-sized per the
paper), routes a workload through Algorithm 1 with live EMA calibration,
and prints per-pool outcomes + router statistics.
"""

from __future__ import annotations

import argparse
import time
from typing import Iterable, Optional

import jax
import numpy as np

from repro.configs import get_config
from repro.core.categories import TRUE_BYTES_PER_TOKEN, Category
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serving import SamplingParams, TwoPoolServer

#: One request: (prompt token ids, prompt bytes, max_output_tokens, category).
WorkItem = tuple[list[int], int, int, int]


def synthetic_workload(
    vocab: int, requests: int, short_cmax: int, long_cmax: int, seed: int
) -> list[WorkItem]:
    """Random prompts; ~10% are short-prompt/long-generation (the paper's
    hard case)."""
    rng = np.random.default_rng(seed)
    out: list[WorkItem] = []
    for _ in range(requests):
        cat = Category(int(rng.integers(0, 4)))
        n = int(rng.integers(4, short_cmax // 2))
        toks = [int(t) for t in rng.integers(0, vocab, n)]
        mx = int(long_cmax * 0.6) if rng.random() < 0.1 else int(rng.integers(2, 12))
        nbytes = int(n * TRUE_BYTES_PER_TOKEN[cat] + rng.normal(0, 4))
        out.append((toks, max(1, nbytes), mx, int(cat)))
    return out


def serve(
    arch: str = "yi-6b",
    *,
    requests: int = 40,
    short_cmax: int = 128,
    long_cmax: int = 512,
    short_slots: int = 8,
    long_slots: int = 2,
    seed: int = 0,
    temperature: float = 0.0,
    reduced: bool = True,
    workload: Optional[Iterable[WorkItem]] = None,
) -> dict:
    """Serve ``workload`` (default: ``requests`` synthetic ones) on two pools.

    ``reduced=False`` builds the architecture at its published widths.
    Weights are random, drawn from ``jax.random.key(seed)``. Returns the
    responses, the server's stats, and the model and params it served.
    """
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(seed))
    srv = TwoPoolServer(
        model,
        params,
        short_cmax=short_cmax,
        long_cmax=long_cmax,
        short_slots=short_slots,
        long_slots=long_slots,
        sampling=SamplingParams(temperature=temperature),
    )
    if workload is None:
        workload = synthetic_workload(
            cfg.vocab, requests, short_cmax, long_cmax, seed
        )

    t0 = time.perf_counter()
    for i, (toks, nbytes, mx, cat) in enumerate(workload):
        srv.submit(i, toks, nbytes, mx, category=cat)
        # interleave arrival with service (continuous batching)
        if i % 4 == 3:
            srv.step()
    srv.run_to_completion()
    responses = srv.responses  # includes completions from interleaved steps
    wall = time.perf_counter() - t0

    stats = srv.stats()
    by_pool = {"short": 0, "long": 0}
    for r in responses:
        by_pool[r.pool] += 1
    print(f"[serve] {len(responses)} responses in {wall:.1f}s")
    print(f"[serve] pool split: {by_pool}")
    print(f"[serve] router: {stats['router']['routed_short']} short, "
          f"{stats['router']['routed_long']} long, "
          f"{stats['router']['spill_count']} spills")
    cal = stats["router"]["calibration"]
    for cat in Category:
        true_c = TRUE_BYTES_PER_TOKEN[cat]
        print(
            f"[serve] calib {cat.name}: learned "
            f"{cal['ratio'][int(cat)]:.2f} (true {true_c:.2f}, "
            f"n={cal['count'][int(cat)]})"
        )
    return {
        "responses": responses,
        "stats": stats,
        "model": model,
        "params": params,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--short-cmax", type=int, default=128)
    ap.add_argument("--long-cmax", type=int, default=512)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument(
        "--full-width",
        action="store_true",
        help="serve the architecture at its published widths "
        "(default: the reduced preset)",
    )
    args = ap.parse_args()
    enable_compile_cache()
    serve(
        args.arch,
        requests=args.requests,
        short_cmax=args.short_cmax,
        long_cmax=args.long_cmax,
        temperature=args.temperature,
        reduced=not args.full_width,
    )


if __name__ == "__main__":
    main()
