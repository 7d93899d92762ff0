"""Plain reference of the fleet simulator's semantics, and the comparison.

A straightforward discrete-event simulation written from the paper's
Appendix A and Algorithm 1, one Python object per instance and one loop
step per engine iteration. It imports nothing of the program.

Semantics, as the program's compiled tier states them:

* routing: per-request budget ``ceil(|r| / max(c_k - gamma*s_k, 0.25)) +
  max_output`` (Eq. 3-5) with the per-category EMA (Eq. 4) folded in
  arrival order over ramped epochs (64 doubling to ``epoch``): requests of
  one epoch see the EMA as of its start. Pool = number of thresholds
  strictly below the budget. No spillover.
* dispatch: the least-loaded instance of the pool (queue + active, lowest
  index on ties); a prompt of ``c_max`` tokens or more is rejected at
  submit. Arrivals win exact-time ties against engine iterations.
* one engine iteration (``t = W + H * n_active``): FIFO admission while a
  slot is free and the prompt's 16-token blocks fit (head of line waits;
  one that can never fit is rejected); one prefill chunk of up to ``C``
  tokens for the oldest prefilling sequence; one decode token for every
  decoding sequence; truncation at ``c_max``; completions free their
  blocks; if block growth exceeds the free blocks, the youngest decoding
  survivors (latest enqueue, first admitted on ties) are preempted until
  it fits and requeued at the head, recompute-style, with their generated
  tokens folded into the prompt.

``ftype`` is the type event times are computed in: ``float`` (float64,
what the configuration states) or ``np.float32`` (the control).
"""

from __future__ import annotations

import bisect
import heapq
import math
from collections import deque

import numpy as np

KV_BLOCK_TOKENS = 16
TOTAL_KV_BLOCKS = 65_536
MIN_RATIO = 0.25


def _blocks(tokens: int) -> int:
    return max(1, -(-tokens // KV_BLOCK_TOKENS))


def route_budgets(cols: dict, cal: dict, epoch: int):
    """Per-request budgets in float64: ``(budget int, real-valued budget)``."""
    n = len(cols["byte_len"])
    k = int(cal["categories"])
    beta, gamma = float(cal["beta"]), float(cal["gamma"])
    ratio, sigma, count = [float(cal["c0"])] * k, [0.0] * k, [0] * k
    budget = np.zeros(n, np.int64)
    real = np.zeros(n, np.float64)
    nbytes = cols["byte_len"].tolist()
    mx = cols["max_output_tokens"].tolist()
    cat = cols["category"].tolist()
    inp = cols["true_input_tokens"].tolist()
    chunk, pos = min(64, epoch), 0
    while pos < n:
        start, pos = pos, min(n, pos + chunk)
        chunk = min(epoch, chunk * 2)
        c_route = [max(r - gamma * s, MIN_RATIO) for r, s in zip(ratio, sigma)]
        for i in range(start, pos):
            x = nbytes[i] / c_route[cat[i]]
            real[i] = x + mx[i]
            budget[i] = math.ceil(x) + mx[i]
        for i in range(start, pos):
            c = cat[i]
            obs = nbytes[i] / inp[i]
            b = beta if count[c] > 0 else 0.0
            ratio[c] = b * ratio[c] + (1.0 - b) * obs
            sigma[c] = b * sigma[c] + (1.0 - b) * abs(obs - ratio[c])
            count[c] += 1
    return budget, real


def pool_choice(budget, real, thresholds):
    """Reference pool per request, and where it is a near-tie.

    A budget within one token of a threshold is a near-tie: the program
    estimates in float32, and a one-token difference there legitimately
    picks the other pool."""
    th = list(thresholds)
    pool = np.array([bisect.bisect_left(th, int(b)) for b in budget], np.int64)
    tie = np.zeros(len(budget), bool)
    for t in th:
        tie |= np.abs(real - t) <= 1.0
        tie |= np.abs(budget - t) <= 1
    return pool, tie


class _Instance:
    __slots__ = ("queue", "active", "free", "total", "carried", "c_max",
                 "n_seq")

    def __init__(self, c_max: int, n_seq: int) -> None:
        self.c_max, self.n_seq = c_max, n_seq
        self.total = min(TOTAL_KV_BLOCKS, n_seq * _blocks(c_max))
        self.free = self.total
        self.queue: deque = deque()  # [rid, input_tokens, enqueue_time]
        self.active: list = []
        self.carried: dict[int, int] = {}

    @property
    def load(self) -> int:
        return len(self.queue) + len(self.active)


def simulate(cols: dict, pools: list[dict], timing: dict, pool_of,
             ftype=float) -> dict[str, np.ndarray]:
    """Run the fleet; ``pools`` in budget order, ``pool_of[i]`` the pool of
    request ``i`` (arrival order). Returns per-request record columns."""
    n = len(cols["arrival_time"])
    w, h = ftype(timing["w_base"]), ftype(timing["h_per_seq"])
    chunk_c = int(timing["prefill_chunk"])
    tiny = ftype(1e-9)
    arr = [ftype(a) for a in cols["arrival_time"].tolist()]
    inp0 = cols["true_input_tokens"].tolist()
    outp = cols["true_output_tokens"].tolist()

    first = [math.nan] * n
    finish = [math.nan] * n
    out = [0] * n
    pre = [0] * n
    trunc = [False] * n
    rej = [False] * n

    insts = [[_Instance(int(p["c_max"]), int(p["n_seq"]))
              for _ in range(int(p["instances"]))] for p in pools]
    heap: list = []
    sleeping = {id(x) for group in insts for x in group}
    counter = 0

    def reject(rid, t):
        first[rid] = finish[rid] = t
        rej[rid] = True

    def step(inst: _Instance, now):
        # admission
        while inst.queue and len(inst.active) < inst.n_seq:
            rid, tokens, enq = inst.queue[0]
            need = _blocks(tokens)
            if need > inst.total:
                inst.queue.popleft()
                reject(rid, now)
                continue
            if need > inst.free:
                break
            inst.queue.popleft()
            inst.free -= need
            # [rid, prompt, enqueue, prefill_rem, decode_rem, generated,
            #  blocks, first_token, preemptions, truncated]
            inst.active.append([rid, tokens, enq, tokens, outp[rid], 0, need,
                                None, inst.carried.get(rid, 0), False])
        if not inst.active:
            return None
        t_iter = w + h * ftype(len(inst.active))
        end = now + t_iter
        for s in inst.active:
            if s[3] > 0:
                s[3] -= min(s[3], chunk_c)
                break
        done, growers = [], []
        for s in inst.active:
            if not (s[3] == 0 and s[4] > 0):
                continue
            if s[7] is None:
                s[7] = end
            s[5] += 1
            s[4] -= 1
            if s[1] + s[5] >= inst.c_max and s[4] > 0:
                s[9] = True
                s[4] = 0
            (done if s[4] == 0 else growers).append(s)
        for s in done:
            inst.active.remove(s)
            inst.free += s[6]
            rid = s[0]
            first[rid] = s[7] if s[7] is not None else end
            finish[rid] = end
            out[rid], pre[rid], trunc[rid] = s[5], s[8], s[9]
        grow = [_blocks(s[1] + s[5]) - s[6] for s in growers]
        demand = sum(grow)
        if demand > inst.free:
            order = sorted(range(len(growers)), key=lambda j: -growers[j][2])
            supply, evicted = inst.free, set()
            for j in order:
                if demand <= supply:
                    break
                demand -= grow[j]
                supply += growers[j][6]
                evicted.add(j)
            victims = [growers[j] for j in sorted(evicted)]
            for s in victims:
                inst.active.remove(s)
                inst.free += s[6]
                s[8] += 1
                inst.carried[s[0]] = s[8]
            for s in reversed(victims):
                inst.queue.appendleft([s[0], s[1] + s[5], s[2]])
            growers = [s for j, s in enumerate(growers) if j not in evicted]
        for s in growers:
            need = _blocks(s[1] + s[5])
            inst.free -= need - s[6]
            s[6] = need
        return t_iter

    ai = 0
    while ai < n or heap:
        if not heap or (ai < n and arr[ai] <= heap[0][0]):
            t = arr[ai]
            group = insts[int(pool_of[ai])]
            inst = min(group, key=lambda x: x.load)
            if inp0[ai] >= inst.c_max:
                reject(ai, t)
            else:
                inst.queue.append([ai, inp0[ai], t])
                if id(inst) in sleeping:
                    sleeping.discard(id(inst))
                    heapq.heappush(heap, (t, counter, inst))
                    counter += 1
            ai += 1
            continue
        now, _, inst = heapq.heappop(heap)
        t_iter = step(inst, now)
        if not inst.queue and not inst.active:
            sleeping.add(id(inst))
        else:
            dt = t_iter if t_iter is not None and t_iter > tiny else tiny
            heapq.heappush(heap, (now + dt, counter, inst))
            counter += 1
    return {
        "first": np.asarray(first, np.float64),
        "finish": np.asarray(finish, np.float64),
        "out": np.asarray(out, np.int64),
        "pre": np.asarray(pre, np.int64),
        "trunc": np.asarray(trunc, bool),
        "rej": np.asarray(rej, bool),
    }


def run_reference(cols: dict, config: dict, thresholds, ftype=float,
                  program_pool=None):
    """Reference records for one lane, plus the routing comparison.

    Near-tie requests (see :func:`pool_choice`) take the program's pool
    when it is one of the two a one-token budget difference allows; every
    other request takes the reference's own pool."""
    sim = config["sim"]
    budget, real = route_budgets(cols, sim["calibrator"], int(sim["epoch"]))
    pool, tie = pool_choice(budget, real, thresholds)
    mismatch = 0
    if program_pool is not None:
        program_pool = np.asarray(program_pool, np.int64)
        legal = np.abs(program_pool - pool) <= 1
        bad = (program_pool != pool) & ~(tie & legal)
        mismatch = int(bad.sum())
        pool = np.where(tie & legal, program_pool, pool)
    rec = simulate(cols, config["pools"], config["timing"], pool, ftype)
    rec["pool"] = pool
    return rec, mismatch


def compare_records(prog: dict, ref: dict) -> dict[str, float]:
    """Numbers compared for one lane: records whose discrete columns differ
    (output tokens, preemptions, truncation, rejection, pool) and the
    widest gap of an event time (first token, finish) in seconds."""
    differ = np.zeros(len(ref["out"]), bool)
    for c in ("out", "pre", "trunc", "rej", "pool"):
        differ |= np.asarray(prog[c]).astype(np.int64) != ref[c].astype(np.int64)
    gap = 0.0
    for c in ("first", "finish"):
        a, b = np.asarray(prog[c], np.float64), ref[c]
        both_nan = np.isnan(a) & np.isnan(b)
        d = np.where(both_nan, 0.0, np.abs(a - b))
        d = np.where(np.isnan(d), np.inf, d)
        gap = max(gap, float(d.max()) if d.size else 0.0)
    return {"records_differing": int(differ.sum()), "time_gap_s": gap}
