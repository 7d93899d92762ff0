"""Iteration-based continuous-batching serving engine (one pool instance).

JAX counterpart of the DES instance model (Appendix A layer 1): ``n_seq``
slots, one decode token per active slot per iteration, prompt prefill on
admission. Static shapes throughout: the decode step is one compiled
program per pool configuration — the short pool and the long pool are
*different compiled programs* with different ``c_max``, which is the paper's
configuration–traffic-matching idea expressed at the XLA level.

Decode parallelism across slots is ``jax.vmap`` over the slot axis with
per-leaf in_axes derived from the model's logical cache axes, so every slot
writes its KV at its own position in one fused step.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model_zoo import Model
from repro.serving.kv_cache import SlotAllocator, SlotKVCache, bucket_length
from repro.serving.sampler import SamplingParams, sample


def build_slot_decode(model: Model, batch_axes: Any):
    """Jitted decode of one token in every slot.

    ``vmap`` of the model's one-sequence ``decode_step`` over the slot
    axis of each decode-state leaf (``batch_axes``, see
    :func:`repro.serving.kv_cache.slot_batch_axes`), so every slot
    writes its KV at its own position in one fused step. Signature:
    ``(params, state, tokens (S,), index (S,)) -> (logits (S, V),
    state)``; the state argument is donated."""

    def single(params, state_slice, token, index):
        state = jax.tree.map(
            lambda x, ax: jnp.expand_dims(x, ax), state_slice, batch_axes
        )
        batch = {"tokens": token[None, None], "index": index}
        logits, new_state = model.decode_step(params, state, batch)
        new_state = jax.tree.map(
            lambda x, ax: jnp.squeeze(x, ax), new_state, batch_axes
        )
        return logits[0], new_state

    vm = jax.vmap(
        single,
        in_axes=(None, batch_axes, 0, 0),
        out_axes=(0, batch_axes),
    )
    return jax.jit(vm, donate_argnums=(1,))


@dataclasses.dataclass
class ServeRequest:
    request_id: int
    tokens: list[int]  # prompt token ids
    max_new_tokens: int
    eos_id: int = -1  # -1 → never stops early


@dataclasses.dataclass
class Completion:
    request_id: int
    prompt_tokens: int  # usage.prompt_tokens — the router's feedback signal
    output_tokens: list[int]
    iterations: int


@dataclasses.dataclass
class _SlotState:
    request: ServeRequest
    length: int  # current context length (prompt + generated)
    remaining: int
    generated: list[int]
    iterations: int = 0


class ServingEngine:
    """One pool instance: admission queue + slot cache + decode loop."""

    def __init__(
        self,
        model: Model,
        params: Any,
        *,
        c_max: int,
        n_slots: int,
        sampling: SamplingParams = SamplingParams(),
        prompt_bucket: int = 64,
    ) -> None:
        if model.cfg.frontend != "tokens":
            raise ValueError("serving engine requires a token-frontend arch")
        self.model = model
        self.params = params
        self.c_max = c_max
        self.n_slots = n_slots
        self.sampling = sampling
        self.prompt_bucket = prompt_bucket
        self.cache = SlotKVCache(model, c_max, n_slots)
        self.alloc = SlotAllocator(n_slots)
        self.queue: deque[ServeRequest] = deque()
        self.slots: dict[int, _SlotState] = {}
        self.rejections = 0
        self.iterations = 0

        self._prefill = jax.jit(model.prefill)
        self._decode = build_slot_decode(model, self.cache.batch_axes)
        self._token_buf = np.zeros((n_slots,), np.int32)
        self._index_buf = np.zeros((n_slots,), np.int32)

    # -- queue ------------------------------------------------------------------
    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    @property
    def active(self) -> int:
        return len(self.slots)

    def submit(self, request: ServeRequest) -> bool:
        """Reject requests whose prompt alone exceeds c_max (paper §1.3)."""
        if len(request.tokens) >= self.c_max:
            self.rejections += 1
            return False
        self.queue.append(request)
        return True

    # -- admission ----------------------------------------------------------------
    def _admit(self) -> None:
        while self.queue and self.alloc.num_free > 0:
            req = self.queue.popleft()
            slot = self.alloc.alloc()
            assert slot is not None
            prompt = np.asarray(req.tokens, np.int32)
            n = len(prompt)
            if self.model.cfg.family in ("dense", "moe", "vlm", "audio"):
                pad = bucket_length(
                    n, multiple=self.prompt_bucket, max_len=self.c_max
                )
                padded = np.zeros((pad,), np.int32)
                padded[:n] = prompt
                batch = {
                    "tokens": jnp.asarray(padded)[None],
                    "last_pos": jnp.asarray([n - 1], jnp.int32),
                }
            else:
                batch = {"tokens": jnp.asarray(prompt)[None]}
            logits, prefill_state = self._prefill(self.params, batch)
            self.cache.insert_prefill(slot, prefill_state)
            first = int(
                sample(logits, jax.random.key(req.request_id), self.sampling)[0]
            )
            self.slots[slot] = _SlotState(
                request=req,
                length=n + 1,
                remaining=req.max_new_tokens - 1,
                generated=[first],
            )
            self._token_buf[slot] = first
            self._index_buf[slot] = n

    # -- one iteration ---------------------------------------------------------
    def step(self, rng: Optional[jax.Array] = None) -> list[Completion]:
        """Admit + decode one token per active slot. Returns completions."""
        self._admit()
        completions: list[Completion] = []
        done_now = [
            s
            for s, st in self.slots.items()
            if st.remaining <= 0 or st.length >= self.c_max
        ]
        for s in done_now:
            completions.append(self._finish(s))
        if not self.slots:
            return completions

        tokens = jnp.asarray(self._token_buf)
        index = jnp.asarray(self._index_buf)
        logits, new_state = self._decode(
            self.params, self.cache.state, tokens, index
        )
        self.cache.update(new_state)
        if rng is None:
            rng = jax.random.key(self.iterations)
        next_tokens = np.asarray(sample(logits, rng, self.sampling))
        self.iterations += 1

        for slot, st in list(self.slots.items()):
            tok = int(next_tokens[slot])
            st.generated.append(tok)
            st.length += 1
            st.remaining -= 1
            st.iterations += 1
            self._token_buf[slot] = tok
            self._index_buf[slot] = st.length - 1
            if (
                st.remaining <= 0
                or st.length >= self.c_max
                or tok == st.request.eos_id
            ):
                completions.append(self._finish(slot))
        return completions

    def _finish(self, slot: int) -> Completion:
        st = self.slots.pop(slot)
        self.alloc.release(slot)
        return Completion(
            request_id=st.request.request_id,
            prompt_tokens=len(st.request.tokens),
            output_tokens=st.generated,
            iterations=st.iterations,
        )

    def run_to_completion(self, max_iters: int = 100_000) -> list[Completion]:
        """Drain queue + slots (examples / tests)."""
        out: list[Completion] = []
        for _ in range(max_iters):
            out.extend(self.step())
            if not self.queue and not self.slots:
                break
        return out
