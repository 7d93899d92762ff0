"""Driver: open-loop serving through ``repro.serving.TwoPoolServer``.

Set-up builds the model at the configuration's widths, makes the weights
on the device from the seed (``bench/weights.py``), builds the server
with the configuration's pools, sets each engine's prompt bucket, and
warms every program the schedule can reach: each prefill bucket up to the
longest prompt, the slot insert for each, both pools' slot decode and the
sampler. It warms through the engines' own ``submit``/``step``, never the
router, so the router's calibration starts cold as it would in service.

The window submits each request of the schedule (``bench/traffic_gen.py``)
when it is due and steps the server whenever it has work, in one thread.
A request's time to first token runs from when it was due to the end of
the ``step`` call in which its first token reached the host; its finish
is the end of the ``step`` that returned it. Requests due in the window
are drained after it for up to ``drain_seconds``. A request still
decoding when the drain ends has its time per output token taken over
the tokens it has (its first token to the end of the last step); one
with no first token by then counts as missing every limit (its latency
runs to the end of the drain) and as failed.

The check replays Algorithm 1's routing in float64 on the observed order
of submissions and completions, checks each output's length, and compares
the logits of a sample of finished requests, the longest among them, with
the plain float32 reference (``bench/reference/transformer.py``).
"""

from __future__ import annotations

import bisect
import gc
import math
import time

import numpy as np

from bench import traffic_gen, weights
from bench.core import Check, jax_seed
from bench.reference import transformer as ref_model


def route_replay(events, sched, cfg: dict) -> dict[int, str]:
    """Pool per request from Algorithm 1 (Eq. 3-5, EMA feedback of
    ``usage.prompt_tokens`` on completion), spillover off, replayed in the
    order the server saw submissions (``("submit", i)``) and completions
    (``("done", i)``). A prompt the short pool cannot hold goes long."""
    srv = cfg["server"]
    cal = srv["calibrator"]
    k, beta, gamma = int(cal["categories"]), cal["beta"], cal["gamma"]
    ratio, sigma, count = [float(cal["c0"])] * k, [0.0] * k, [0] * k
    short = next(p for p in cfg["pools"] if p["name"] == "short")
    pool = {}
    for kind, i in events:
        c = int(sched.category[i])
        if kind == "submit":
            c_route = max(ratio[c] - gamma * sigma[c], 0.25)
            budget = (math.ceil(int(sched.byte_len[i]) / c_route)
                      + int(sched.max_new[i]))
            idx = bisect.bisect_left([int(srv["b_short"])], budget)
            name = "short" if idx == 0 else "long"
            if name == "short" and sched.prompt_len[i] >= short["c_max"]:
                name = "long"
            pool[i] = name
        else:
            obs = int(sched.byte_len[i]) / int(sched.prompt_len[i])
            b = beta if count[c] > 0 else 0.0
            ratio[c] = b * ratio[c] + (1.0 - b) * obs
            sigma[c] = b * sigma[c] + (1.0 - b) * abs(obs - ratio[c])
            count[c] += 1
    return pool


class Driver:
    trace_op_line = "XLA Ops"

    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.attempted = 0
        self.failed = 0
        tr = ctx.config["trace"]
        ctx.tracer.start_s = tr["start_frac"] * ctx.seconds
        ctx.tracer.length_s = tr["seconds"]

    # -- set-up -----------------------------------------------------------------
    def setup(self) -> None:
        import jax

        from repro.configs.base import ArchConfig
        from repro.models import Model
        from repro.serving import SamplingParams, TwoPoolServer
        from repro.serving.engine import ServeRequest
        from repro.serving.kv_cache import bucket_length

        cfg = self.ctx.config
        self.m = cfg["model"]
        arch = ArchConfig(name=cfg["name"], **{
            k: v for k, v in self.m.items() if k != "dtype"})
        self.model = Model(arch)
        self.params = weights.make(self.model.abstract(), cfg["init"],
                                   jax_seed(self.ctx.seed))
        pools = {p["name"]: p for p in cfg["pools"]}
        srv_cfg = cfg["server"]
        self.srv = TwoPoolServer(
            self.model, self.params,
            short_cmax=pools["short"]["c_max"],
            long_cmax=pools["long"]["c_max"],
            short_slots=pools["short"]["slots"],
            long_slots=pools["long"]["slots"],
            b_short=srv_cfg["b_short"],
            bytes_per_token_hint=srv_cfg["calibrator"]["c0"],
            sampling=SamplingParams(temperature=0.0),
            spillover=srv_cfg["spillover"],
            queue_limit=srv_cfg["queue_limit"],
        )
        self.engines = {"short": self.srv.short_engine,
                        "long": self.srv.long_engine}
        for name, eng in self.engines.items():
            eng.prompt_bucket = pools[name]["prompt_bucket"]

        self.sched = traffic_gen.GENERATORS[self.ctx.traffic["generator"]](
            self.ctx.traffic, self.ctx.seconds, self.ctx.seed, self.m["vocab"])
        longest = int(self.sched.prompt_len.max())
        rid = -1
        for name, eng in self.engines.items():
            top = bucket_length(longest, multiple=eng.prompt_bucket,
                                max_len=eng.c_max)
            for b in range(eng.prompt_bucket, top + 1, eng.prompt_bucket):
                n = min(b, eng.c_max - 1)
                eng.submit(ServeRequest(rid, [1] * n, max_new_tokens=2))
                rid -= 1
                eng.run_to_completion()
        jax.block_until_ready(self.engines["short"].cache.state)

    # -- window -----------------------------------------------------------------
    def window(self, seconds: float) -> dict:
        srv, sched, tracer = self.srv, self.sched, self.ctx.tracer
        n = len(sched)
        arrival = sched.arrival
        first, finish, ntok, pool_of = {}, {}, {}, {}
        events = []
        tokens_in_window = 0
        deadline = float(self.ctx.config["drain_seconds"])
        traced = {"steps": 0, "prefill_flops": 0, "decode_flops": 0,
                  "decode_calls": 0, "decode_bytes": 0}

        def busy() -> bool:
            return any(e.queue_depth or e.active for e in self.engines.values())

        i = 0
        t0 = time.perf_counter()
        now = 0.0
        while True:
            now = time.perf_counter() - t0
            while i < n and arrival[i] <= now:
                with tracer.span("bench.submit"):
                    srv.submit(i, sched.prompts[i], int(sched.byte_len[i]),
                               int(sched.max_new[i]),
                               category=int(sched.category[i]))
                events.append(("submit", i))
                i += 1
            if not busy():
                if i == n:
                    break
                time.sleep(max(0.0, arrival[i] - now))
                continue
            if now > seconds + deadline:
                break
            tracer.tick(now)
            tracing = tracer.state == "tracing"
            if tracing:
                before = {k: e.iterations for k, e in self.engines.items()}
            with tracer.span("bench.step"):
                done = srv.step()
            now = time.perf_counter() - t0
            for eng in self.engines.values():
                for st in eng.slots.values():
                    first.setdefault(st.request.request_id, now)
            for r in done:
                first.setdefault(r.request_id, now)
                finish[r.request_id] = now
                ntok[r.request_id] = r.output_tokens
                pool_of[r.request_id] = r.pool
                events.append(("done", r.request_id))
            if now <= seconds:
                tokens_in_window = (
                    sum(len(t) for t in ntok.values())
                    + sum(len(st.generated) for e in self.engines.values()
                          for st in e.slots.values()))
            if tracing:
                self._count_traced(traced, before, done)
        t_end = now
        self.events, self.outputs, self.pool_of = events, ntok, dict(pool_of)
        # requests still in the engines when the drain ended
        live = {}
        for name, eng in self.engines.items():
            for st in eng.slots.values():
                live[st.request.request_id] = len(st.generated)
                self.pool_of[st.request.request_id] = name
            for req in eng.queue:
                self.pool_of[req.request_id] = name

        missing = [j for j in range(n) if j not in first]
        self.attempted, self.failed = n, len(missing)
        ttft, tpot = [], []
        for j in range(n):
            if j not in first:
                ttft.append(t_end - arrival[j])
                tpot.append(t_end - arrival[j])
                continue
            ttft.append(first[j] - arrival[j])
            k, end = ((len(ntok[j]), finish[j]) if j in finish
                      else (live.get(j, 0), t_end))
            if k >= 2:
                tpot.append((end - first[j]) / (k - 1))
        self.ttft = ttft  # per request, in arrival order (knee sweeps read it)
        self.ctx.counters.update(traced)
        self.ctx.counters["requests"] = n
        self.ctx.log(f"{n} requests due, {len(finish)} finished, {len(live)} "
                     f"still decoding, {len(missing)} without a first token; "
                     f"drain ended {t_end - seconds!r} s after the window; "
                     f"pools { {p: list(self.pool_of.values()).count(p) for p in ('short', 'long')} }")
        return {
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "tpot_p95_ms": 1e3 * float(np.percentile(tpot, 95)),
            "out_tok_per_s": tokens_in_window / seconds,
        }

    def _count_traced(self, traced, before, done) -> None:
        """Useful work of one traced step, for the per-layer readers."""
        from bench import flops

        m = self.m
        traced["steps"] += 1
        for name, eng in self.engines.items():
            calls = eng.iterations - before[name]
            if calls == 0:
                continue
            # positions each live slot's decode attended, its new one included
            ctxs = [st.length - 1 for st in eng.slots.values()]
            ctxs += [r.prompt_tokens + len(r.output_tokens) - 1
                     for r in done if r.pool == name and len(r.output_tokens) > 1]
            # requests whose first token came from this step's prefill
            fresh = [len(st.request.tokens) for st in eng.slots.values()
                     if len(st.generated) == 2]
            fresh += [r.prompt_tokens for r in done
                      if r.pool == name and len(r.output_tokens) == 2]
            traced["prefill_flops"] += sum(flops.prefill_flops(m, p)
                                           for p in fresh)
            traced["decode_calls"] += calls
            traced["decode_flops"] += sum(flops.token_flops(m, c) for c in ctxs)
            traced["decode_bytes"] += flops.decode_bytes(m, ctxs)

    # -- check ------------------------------------------------------------------
    def check(self) -> list[Check]:
        cfg, sched = self.ctx.config, self.sched
        limits = cfg["checks"]
        want = route_replay(self.events, sched, cfg)
        route_mismatch = sum(self.pool_of[j] != want[j] for j in self.pool_of)
        self.ctx.log(f"pool choice of {len(self.pool_of)} requests replayed")
        cmax = {p["name"]: p["c_max"] for p in cfg["pools"]}
        length_mismatch = sum(
            len(toks) != min(int(sched.max_new[j]),
                             cmax[self.pool_of[j]] - int(sched.prompt_len[j]))
            for j, toks in self.outputs.items())

        rng = np.random.default_rng(self.ctx.seed)
        done = sorted(self.outputs)
        gap = math.inf
        if done:
            longest = max(done, key=lambda j: sched.prompt_len[j]
                          + len(self.outputs[j]))
            others = [j for j in done if j != longest]
            k = min(len(others), int(cfg["check_requests"]) - 1)
            sample = [longest] + rng.choice(others, k, replace=False).tolist()
            self.sample = sample
            # free the server's caches before the reference runs
            del self.srv, self.engines
            gc.collect()
            gap = 0.0
            for j in sample:
                g = ref_model.logit_gaps(self.params, self.m, sched.prompts[j],
                                         self.outputs[j])
                gap = max(gap, float(g.max()))
            self.ctx.log(f"logits of {len(sample)} requests, "
                         f"{sum(len(self.outputs[j]) for j in sample)} served "
                         f"tokens, compared with the reference")
        return [
            Check("route_mismatch", float(route_mismatch),
                  float(limits["route_mismatch"])),
            Check("length_mismatch", float(length_mismatch),
                  float(limits["length_mismatch"])),
            Check("logit_gap", gap, float(limits["logit_gap"])),
        ]
