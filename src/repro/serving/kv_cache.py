"""Slot-based KV cache management for the JAX serving engine.

Each pool instance reserves ``n_seq`` slots of ``c_max`` tokens — precisely
the provisioning rule of paper Eq. 1–2 (the quantity the short pool
right-sizes). Model decode states live in a single batched pytree whose
batch axis is the slot index; prefill results are inserted into a slot with
``dynamic_update_slice`` along the per-leaf batch/seq axes derived from the
model's logical cache axes.

The block-table paged pool (``repro.kernels.paged_attention``) is the
TPU-kernel-level counterpart; the slot layout here is its static-shape
engine-level wrapper (DESIGN.md §3).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeCell
from repro.models.model_zoo import Model


@dataclasses.dataclass
class SlotAllocator:
    """Host-side free-list of sequence slots."""

    n_slots: int

    def __post_init__(self) -> None:
        self.free: list[int] = list(range(self.n_slots))[::-1]
        self.used: set[int] = set()

    def alloc(self) -> Optional[int]:
        if not self.free:
            return None
        slot = self.free.pop()
        self.used.add(slot)
        return slot

    def release(self, slot: int) -> None:
        if slot not in self.used:
            raise ValueError(f"slot {slot} not allocated")
        self.used.discard(slot)
        self.free.append(slot)

    @property
    def num_free(self) -> int:
        return len(self.free)


def slot_cell(c_max: int, n_slots: int) -> ShapeCell:
    """The decode shape cell of one pool: ``n_slots`` x ``c_max`` tokens."""
    return ShapeCell(
        name="serving", kind="decode", seq_len=c_max, global_batch=n_slots
    )


def slot_batch_axes(model: Model, c_max: int, n_slots: int) -> Any:
    """Per-leaf slot axis of the decode state (``None``: not batched).

    The position of ``"serve_batch"`` in the model's logical cache axes;
    computed from abstract shapes, so nothing is allocated."""
    return jax.tree.map(
        lambda ax: ax.index("serve_batch") if "serve_batch" in ax else None,
        model.cache_axes(slot_cell(c_max, n_slots)),
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(a, (str, type(None))) for a in x),
    )


class SlotKVCache:
    """Batched decode-state tree with slot-indexed insertion."""

    def __init__(self, model: Model, c_max: int, n_slots: int) -> None:
        self.model = model
        self.c_max = c_max
        self.n_slots = n_slots
        self.state = model.init_cache(slot_cell(c_max, n_slots))
        self.batch_axes = slot_batch_axes(model, c_max, n_slots)

    def insert_prefill(self, slot: int, prefill_state: Any) -> None:
        """Write a single-sequence prefill state (batch dim 1) into a slot."""

        def write(target, src, batch_axis):
            if batch_axis is None:
                return target
            start = [0] * target.ndim
            start[batch_axis] = slot
            # pad the seq axis difference implicitly: dynamic_update_slice
            # accepts a smaller update block.
            return jax.lax.dynamic_update_slice(
                target, src.astype(target.dtype), tuple(start)
            )

        self.state = jax.tree.map(
            write, self.state, prefill_state, self.batch_axes
        )

    def update(self, new_state: Any) -> None:
        self.state = new_state


def bucket_length(n: int, *, multiple: int = 128, max_len: int = 1 << 20) -> int:
    """Round a prompt length up to the next bucket (limits recompiles)."""
    b = ((max(1, n) + multiple - 1) // multiple) * multiple
    return min(b, max_len)
