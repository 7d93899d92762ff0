"""Plain reference of the fleet simulator for pools that state their own
per-instance KV block budget.

A copy of ``fleet_des.simulate`` and ``fleet_des.run_reference`` whose one
departure is the budget: an instance of a pool serves from
``min(kv_blocks, n_seq * ceil(c_max / 16))`` blocks, where ``kv_blocks``
is the pool's own entry in the configuration (the KV pages its
accelerators hold), instead of ``min(65,536, ...)`` for every pool.
Routing (``route_budgets``, ``pool_choice``) and the comparison
(``compare_records``) are ``fleet_des``'s own. It imports nothing of the
program. ``bench/tests/test_bench_azure10k_cell.py`` ties it to
``fleet_des``: with every budget at 65,536 the records are identical.

``ftype`` is the type event times are computed in: ``float`` (float64,
what the configuration states) or ``np.float32`` (the control).
"""

from __future__ import annotations

import heapq
import math
from collections import deque

import numpy as np

# compare_records is imported for the drivers that check against this copy
from bench.reference.fleet_des import (compare_records, pool_choice,
                                       route_budgets)

KV_BLOCK_TOKENS = 16


def _blocks(tokens: int) -> int:
    return max(1, -(-tokens // KV_BLOCK_TOKENS))


class _Instance:
    __slots__ = ("queue", "active", "free", "total", "carried", "c_max",
                 "n_seq")

    def __init__(self, c_max: int, n_seq: int, kv_blocks: int) -> None:
        self.c_max, self.n_seq = c_max, n_seq
        self.total = min(kv_blocks, n_seq * _blocks(c_max))
        self.free = self.total
        self.queue: deque = deque()  # [rid, input_tokens, enqueue_time]
        self.active: list = []
        self.carried: dict[int, int] = {}

    @property
    def load(self) -> int:
        return len(self.queue) + len(self.active)


def simulate(cols: dict, pools: list[dict], timing: dict, pool_of,
             ftype=float) -> dict[str, np.ndarray]:
    """Run the fleet; ``pools`` in budget order, each with its
    ``kv_blocks``, and ``pool_of[i]`` the pool of request ``i`` (arrival
    order). Returns per-request record columns."""
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

    insts = [[_Instance(int(p["c_max"]), int(p["n_seq"]), int(p["kv_blocks"]))
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
