"""Traffic generation for the benchmark: one general generator per kind of mix.

The length distributions are a copy of the program's bucketed Azure and
LMSYS CDFs and of its stationary Poisson trace recipe (``repro.traces``),
kept here so that no later change to the program can move the yardstick.
``bench/tests/test_bench_traffic.py`` pins the copy to the original bit
for bit.

Two generators, named by the ``generator`` key of a traffic file:

``fleet_trace``
    The paper's trace (Appendix A): Poisson arrivals at ``rate``, totals
    from the CDF, a clipped-normal input/output split, a category and a
    prompt byte length per request; with ``sizes_seed``, one fixed draw
    that the seed permutes. ``trace_columns(params, seed)``.
``open_loop``
    A serving schedule whose set of request sizes and arrival gaps is the
    same for every seed: sizes are the CDF's stratified quantiles, split
    and categorised with the file's fixed ``sizes_seed``, and ``--seed``
    only permutes them and draws the prompt token ids.
    ``open_loop_schedule(params, seconds, seed, vocab)``.
"""

from __future__ import annotations

import bisect
import dataclasses

import numpy as np

# Category ids, ground-truth bytes per token and their per-request noise
# (paper Table 4; copied from repro.core.categories).
ENGLISH_PROSE, SOURCE_CODE, CJK_TEXT, MIXED_OTHER = 0, 1, 2, 3
TRUE_BYTES_PER_TOKEN = {0: 4.48, 1: 3.52, 2: 2.01, 3: 3.81}
BYTES_PER_TOKEN_STD = {0: 0.35, 1: 0.40, 2: 0.20, 3: 0.55}

CATEGORY_MIX = {
    "azure": {0: 0.55, 1: 0.25, 2: 0.08, 3: 0.12},
    "lmsys": {0: 0.50, 1: 0.12, 2: 0.22, 3: 0.16},
}


@dataclasses.dataclass(frozen=True)
class BucketCDF:
    """Piecewise-uniform CDF over total token counts."""

    name: str
    edges: tuple[int, ...]
    cum: tuple[float, ...]
    out_frac_mu: float
    out_frac_sigma: float
    out_frac_clip: tuple[float, float] = (0.02, 0.95)

    def inverse(self, u: float) -> float:
        u = min(max(u, 0.0), 1.0)
        idx = min(bisect.bisect_left(self.cum, u), len(self.cum) - 1)
        lo_edge = 0 if idx == 0 else self.edges[idx - 1]
        lo_cum = 0.0 if idx == 0 else self.cum[idx - 1]
        hi_edge, hi_cum = self.edges[idx], self.cum[idx]
        if hi_cum <= lo_cum:
            return float(hi_edge)
        frac = (u - lo_cum) / (hi_cum - lo_cum)
        return lo_edge + frac * (hi_edge - lo_edge)

    def totals_at(self, u: np.ndarray) -> np.ndarray:
        totals = np.array([self.inverse(v) for v in u])
        return np.maximum(2, np.round(totals)).astype(np.int64)

    def split(self, rng: np.random.Generator, totals: np.ndarray):
        frac = rng.normal(self.out_frac_mu, self.out_frac_sigma, size=len(totals))
        frac = np.clip(frac, *self.out_frac_clip)
        l_out = np.maximum(1, np.round(totals * frac)).astype(np.int64)
        l_in = np.maximum(1, totals - l_out)
        return l_in, l_out


CDFS = {
    "azure": BucketCDF(
        "azure",
        edges=(64, 256, 512, 1024, 2048, 4096, 8192, 16384, 32768, 65536),
        cum=(0.06, 0.2815, 0.4815, 0.6815, 0.8015, 0.8815, 0.917, 0.960,
             0.987, 1.0),
        out_frac_mu=0.10, out_frac_sigma=0.05,
    ),
    "lmsys": BucketCDF(
        "lmsys",
        edges=(32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384),
        cum=(0.10, 0.30, 0.586, 0.786, 0.885, 0.952, 0.9860, 0.9970,
             0.99935, 1.0),
        out_frac_mu=0.75, out_frac_sigma=0.10,
    ),
}


def _categories(rng: np.random.Generator, trace: str, n: int) -> np.ndarray:
    mix = CATEGORY_MIX[trace]
    cats = np.array(list(mix), dtype=np.int64)
    p = np.array([mix[int(c)] for c in cats], dtype=np.float64)
    return rng.choice(cats, size=n, p=p / p.sum())


def _byte_lens(rng: np.random.Generator, l_in, cats) -> np.ndarray:
    mu = np.array([TRUE_BYTES_PER_TOKEN[int(c)] for c in cats])
    sd = np.array([BYTES_PER_TOKEN_STD[int(c)] for c in cats])
    ratio = np.maximum(0.5, rng.normal(mu, sd))
    return np.maximum(1, np.round(l_in * ratio)).astype(np.int64)


def trace_columns(params: dict, seed: int) -> dict[str, np.ndarray]:
    """The paper's stationary trace as columns (``fleet_trace`` generator).

    Draws from one ``default_rng(seed)`` in the program's order: arrival
    gaps, totals, split, categories, bytes. ``max_output_tokens`` equals
    the realised output (the paper's exact caps).

    With ``sizes_seed`` in ``params`` the draws come from that fixed seed
    instead, and ``seed`` only permutes which request comes at each
    arrival: every seed replays the same arrivals and the same multiset
    of requests, so the seed reorders the work without changing it."""
    cdf = CDFS[params["trace"]]
    n = int(params["requests"])
    rng = np.random.default_rng(params.get("sizes_seed", seed))
    gaps = rng.exponential(1.0 / float(params["rate"]), size=n)
    totals = cdf.totals_at(rng.uniform(size=n))
    l_in, l_out = cdf.split(rng, totals)
    cats = _categories(rng, params["trace"], n)
    nbytes = _byte_lens(rng, l_in, cats)
    order = np.arange(n)
    if "sizes_seed" in params:
        perm = np.random.default_rng(seed)
        order = perm.permutation(n)
    return {
        "request_id": np.arange(n, dtype=np.int64),
        "byte_len": nbytes[order],
        "max_output_tokens": l_out[order].astype(np.int64),
        "category": cats[order].astype(np.int64),
        "arrival_time": np.cumsum(gaps).astype(np.float64),
        "true_input_tokens": l_in[order].astype(np.int64),
        "true_output_tokens": l_out[order].astype(np.int64),
    }


@dataclasses.dataclass
class Schedule:
    """An open-loop serving schedule: request i is due at ``arrival[i]``
    seconds after the window opens."""

    arrival: np.ndarray  # (N,) float64, ascending, all < seconds
    prompt_len: np.ndarray  # (N,) int64
    max_new: np.ndarray  # (N,) int64
    category: np.ndarray  # (N,) int64
    byte_len: np.ndarray  # (N,) int64
    prompts: list  # N lists of token ids

    def __len__(self) -> int:
        return len(self.arrival)


def open_loop_schedule(params: dict, seconds: float, seed: int,
                       vocab: int) -> Schedule:
    """Poisson-like arrivals at ``rate`` over ``seconds``, fixed work.

    ``N = round(rate * seconds)`` requests. Their sizes are the trace
    CDF's quantiles at ``(i + 1/2) / N``; the split into prompt and
    output, the category, the byte length and the arrival gaps are drawn
    once from ``sizes_seed``. So every seed serves the same multiset of
    requests and gaps; ``seed`` permutes both and draws the token ids."""
    cdf = CDFS[params["trace"]]
    n = max(1, int(round(float(params["rate"]) * seconds)))
    fixed = np.random.default_rng(int(params["sizes_seed"]))
    totals = cdf.totals_at((np.arange(n) + 0.5) / n)
    l_in, l_out = cdf.split(fixed, totals)
    cats = _categories(fixed, params["trace"], n)
    nbytes = _byte_lens(fixed, l_in, cats)
    gaps = fixed.exponential(1.0, size=n + 1)

    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    gaps = gaps[np.concatenate([rng.permutation(n), [n]])]
    arrival = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
    prompts = [rng.integers(0, vocab, int(k)).tolist() for k in l_in[order]]
    return Schedule(
        arrival=arrival, prompt_len=l_in[order], max_new=l_out[order],
        category=cats[order], byte_len=nbytes[order], prompts=prompts,
    )


#: The generators a traffic file can name.
GENERATORS = {"fleet_trace": trace_columns, "open_loop": open_loop_schedule}
