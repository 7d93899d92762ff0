"""Pallas TPU paged attention (decode): one query token per sequence against
a block-table-indirected KV page pool.

This is the TPU adaptation of vLLM's PagedAttention (DESIGN.md §3): pages
are 16-token KV blocks in a global HBM pool; the per-sequence block table is
a *scalar-prefetch* operand, so the page id feeds the BlockSpec index map and
Mosaic can schedule the HBM→VMEM page streams ahead of compute. Pages past a
sequence's length are skipped with ``pl.when`` — the cost of a decode step
scales with the *actual* context, which is exactly the short-pool advantage
the paper's cost model banks on (Eq. 1–2).

Grid: (batch, kv_heads, pages_per_seq); the page dimension is sequential and
carries online-softmax accumulators in VMEM scratch. All G = H/K query heads
of one KV head are processed together as a (G, D) tile.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(
    # scalar-prefetch operands
    block_tables_ref,  # (B, pages_per_seq) int32 (SMEM)
    lengths_ref,  # (B,) int32 (SMEM)
    # array operands
    q_ref,  # (1, 1, G, D)
    k_ref,  # (1, page, 1, D) — bf16/f32, or int8 with scale refs below
    v_ref,  # (1, page, 1, D)
    *rest,  # [k_scale_ref, v_scale_ref,] o_ref, m_ref, l_ref, acc_ref
    page_size: int,
    pages_per_seq: int,
    scale: float,
    quantized: bool = False,
):
    if quantized:
        k_scale_ref, v_scale_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    base = j * page_size

    @pl.when(base < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
        k = k_ref[0, :, 0].astype(jnp.float32)  # (page, D)
        v = v_ref[0, :, 0].astype(jnp.float32)
        if quantized:
            # int8 pages: dequantize in VMEM after the (half-sized) HBM read
            k = k * k_scale_ref[0, :, 0].astype(jnp.float32)
            v = v * v_scale_ref[0, :, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G, page)
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pages_per_seq - 1)
    def _finish():
        o_ref[0, 0, :, :] = (
            acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        ).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jax.Array,  # (B, H, D) — single decode token per sequence
    k_pages: jax.Array,  # (P, page, K, D) global page pool (bf16/f32/int8)
    v_pages: jax.Array,  # (P, page, K, D)
    block_tables: jax.Array,  # (B, pages_per_seq) int32
    lengths: jax.Array,  # (B,) int32
    *,
    k_scales: jax.Array | None = None,  # (P, page, K, 1) for int8 pages
    v_scales: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    _, page, n_kv, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    g = h // n_kv
    scale = 1.0 / math.sqrt(d)
    quantized = k_pages.dtype == jnp.int8
    if quantized and (k_scales is None or v_scales is None):
        raise ValueError("int8 pages require k_scales/v_scales")

    # (B, H, D) → (B, K, G, D): all query heads of one KV head together.
    q4 = q.reshape(b, n_kv, g, d)

    kernel = functools.partial(
        _paged_kernel,
        page_size=page,
        pages_per_seq=pages_per_seq,
        scale=scale,
        quantized=quantized,
    )

    page_spec = pl.BlockSpec(
        (1, page, 1, d), lambda b_, kv, j, bt, ln: (bt[b_, j], 0, kv, 0)
    )
    scale_spec = pl.BlockSpec(
        (1, page, 1, 1), lambda b_, kv, j, bt, ln: (bt[b_, j], 0, kv, 0)
    )
    in_specs = [
        pl.BlockSpec((1, 1, g, d), lambda b_, kv, j, bt, ln: (b_, kv, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [block_tables, lengths, q4, k_pages, v_pages]
    if quantized:
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales, v_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_kv, pages_per_seq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda b_, kv, j, bt, ln: (b_, kv, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )

    out_dtype = q.dtype
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return out.reshape(b, h, d)
