"""Pallas TPU Mamba-2 SSD chunk scan.

The sub-quadratic sequence mixer of the hybrid/ssm architectures (zamba2,
and the same dual form as xLSTM's mLSTM). Each (batch, head) pair scans its
chunks sequentially, carrying the (P, N) state in VMEM scratch; within a
chunk the recurrence is the dual quadratic form — two MXU matmuls over a
(Q, Q) decay-masked Gram matrix.

Inputs are pre-projected at the ops layer: the kernel receives per-step
``log_a = A·dt`` (decay, already multiplied) and ``dt·x`` folding so the
kernel is a pure scan — this keeps it reusable for any gated-linear-
recurrence model (DESIGN.md §3 hardware-adaptation note).

Grid: (batch, heads, chunks) with chunks sequential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,  # (1, 1, Q, P) — dt·x already folded
    loga_ref,  # (1, 1, Q, 128) — log decay per step (broadcast on lanes)
    b_ref,  # (1, Q, N)
    c_ref,  # (1, Q, N)
    y_ref,  # (1, 1, Q, P) out
    s_out_ref,  # (1, 1, P, N) out — final state
    state_ref,  # VMEM (P, N) f32 scratch
    *,
    chunk: int,
    num_chunks: int,
):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)  # (Q, P)
    log_a = loga_ref[0, 0, :, :1].astype(jnp.float32)  # (Q, 1)
    bmat = b_ref[0].astype(jnp.float32)  # (Q, N)
    cmat = c_ref[0].astype(jnp.float32)  # (Q, N)

    cum = jnp.cumsum(log_a, axis=0)  # (Q, 1) inclusive
    # intra-chunk: y[i] = Σ_{j≤i} (C_i·B_j) exp(cum_i − cum_j) x_j
    cb = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q)
    seg = cum - cum.T  # (Q, Q) cum_i - cum_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    y = jax.lax.dot_general(
        cb * decay, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (Q, P)

    # cross-chunk read: y[i] += (C_i · S_prev^T) exp(cum_i)
    s_prev = state_ref[...]  # (P, N)
    y_cross = jax.lax.dot_general(
        cmat, s_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (Q, P)
    y = y + y_cross * jnp.exp(cum)

    # state update: S = exp(total) S_prev + Σ_j exp(total − cum_j) x_j B_j^T
    total = cum[-1:, :]  # (1, 1)
    w = jnp.exp(total - cum)  # (Q, 1)
    s_add = jax.lax.dot_general(
        x * w, bmat, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (P, N)
    state_ref[...] = jnp.exp(total) * s_prev + s_add

    y_ref[0, 0, :, :] = y.astype(y_ref.dtype)

    @pl.when(c_idx == num_chunks - 1)
    def _emit_state():
        s_out_ref[0, 0, :, :] = state_ref[...].astype(s_out_ref.dtype)


def ssd_scan_pallas(
    x: jax.Array,  # (B, H, L, P) — pre-multiplied by dt
    log_a: jax.Array,  # (B, H, L) — A·dt per step
    b_mat: jax.Array,  # (B, L, N)
    c_mat: jax.Array,  # (B, L, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B,H,L,P), final_state (B,H,P,N))."""
    bsz, h, l, p = x.shape
    n = b_mat.shape[-1]
    chunk = min(chunk, l)
    if l % chunk:
        raise ValueError(f"L={l} must divide chunk={chunk}")
    nck = l // chunk

    # lanes-broadcast the decay so the block keeps a 128 minor dimension
    loga4 = jnp.broadcast_to(log_a[..., None], (bsz, h, l, 128))

    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nck)
    y, s_final = pl.pallas_call(
        kernel,
        grid=(bsz, h, nck),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, chunk, 128), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c: (b_, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c: (b_, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, c: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, l, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, loga4, b_mat, c_mat)
    return y, s_final
