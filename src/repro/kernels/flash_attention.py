"""Pallas TPU flash attention (prefill): causal, GQA/MQA via index-map
head folding — no KV replication in HBM or VMEM.

Grid: (batch, q_heads, q_blocks, kv_blocks); the kv_blocks dimension is the
sequential ("arbitrary") one, carrying the online-softmax accumulators in
VMEM scratch. BlockSpecs tile HBM→VMEM in (block, head_dim) tiles aligned to
the MXU (head_dim is 64/80/128/256 for our archs; q/kv blocks default 512).
Causal blocks above the diagonal are skipped with ``pl.when`` (no FLOPs, no
HBM reads for masked-out tiles beyond the stream), halving causal work vs a
masked dense scan.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # (1, 1, bq, D)
    k_ref,  # (1, 1, bk, D)
    v_ref,  # (1, 1, bk, D)
    o_ref,  # (1, 1, bq, D)
    m_ref,  # VMEM (bq, 128) f32
    l_ref,  # VMEM (bq, 128) f32
    acc_ref,  # VMEM (bq, D) f32
    *,
    causal: bool,
    block_q: int,
    block_k: int,
    scale: float,
    num_kv_blocks: int,
):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)

        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)

        m_prev = m_ref[:, :1]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    # causal: with block_q == block_k, block (i, j) contributes iff j <= i —
    # blocks above the diagonal are skipped entirely.
    if causal:
        pl.when(j * block_k <= i * block_q)(_compute)
    else:
        _compute()

    @pl.when(j == num_kv_blocks - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0, 0, :, :] = (
            acc_ref[...] / jnp.maximum(l, 1e-30)
        ).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, H, Lq, D)
    k: jax.Array,  # (B, K, Lk, D)
    v: jax.Array,  # (B, K, Lk, D)
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Head-major flash attention; q heads fold onto kv heads via index map."""
    b, h, lq, d = q.shape
    _, n_kv, lk, _ = k.shape
    g = h // n_kv
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    if lq % block_q or lk % block_k:
        raise ValueError("sequence lengths must divide block sizes")
    if causal and block_q != block_k:
        raise ValueError("causal path requires block_q == block_k")
    nq, nk = lq // block_q, lk // block_k
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        scale=scale,
        num_kv_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
