"""Shared neural building blocks (pure JAX, bf16 compute / fp32 accumulate).

Includes the O(L)-memory chunked flash attention used for 32k prefill and
4k training (the pure-jnp counterpart of ``repro.kernels.flash_attention``)
and the cache-reading decode attention (counterpart of
``repro.kernels.paged_attention``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain

# ---------------------------------------------------------------------------
# Norms / MLP
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def mlp(x: jax.Array, params: dict, activation: str) -> jax.Array:
    """Gated (swiglu/geglu) or plain (gelu) feed-forward."""
    if activation in ("swiglu", "geglu"):
        gate = jnp.einsum("...d,df->...f", x, params["w_gate"])
        up = jnp.einsum("...d,df->...f", x, params["w_up"])
        act = jax.nn.silu(gate) if activation == "swiglu" else jax.nn.gelu(gate)
        hidden = act * up
    elif activation == "gelu":
        hidden = jax.nn.gelu(jnp.einsum("...d,df->...f", x, params["w_up"]))
    else:
        raise ValueError(f"unknown activation {activation!r}")
    hidden = constrain(hidden, (None, None, "ffn"))
    return jnp.einsum("...f,fd->...d", hidden, params["w_down"])


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE and multimodal M-RoPE)
# ---------------------------------------------------------------------------


def rope_angles(
    positions: jax.Array, head_dim: int, theta: float
) -> tuple[jax.Array, jax.Array]:
    """positions (..., L) → cos/sin (..., L, head_dim/2) in fp32."""
    half = head_dim // 2
    freqs = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def mrope_angles(
    positions: jax.Array,  # (3, B, L) — temporal / height / width streams
    head_dim: int,
    theta: float,
    sections: tuple[int, ...],
) -> tuple[jax.Array, jax.Array]:
    """M-RoPE (Qwen2-VL): rotary pairs are split into sections, each driven
    by its own positional stream. Returns cos/sin (B, L, head_dim/2)."""
    half = head_dim // 2
    if sum(sections) != half:
        raise ValueError(f"mrope sections {sections} must sum to {half}")
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    # effective position per pair: stream index for each frequency slot
    stream_idx = jnp.concatenate(
        [jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)]
    )  # (half,)
    # positions: (3, B, L) → per-pair positions (B, L, half)
    pos_eff = jnp.take(positions, stream_idx, axis=0)  # (half, B, L)
    pos_eff = jnp.moveaxis(pos_eff, 0, -1).astype(jnp.float32)  # (B, L, half)
    ang = pos_eff * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (..., L, H, D); cos/sin broadcastable to (..., L, 1, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :] if cos.ndim == x.ndim - 1 else cos
    s = sin[..., None, :] if sin.ndim == x.ndim - 1 else sin
    out1 = x1 * c - x2 * s
    out2 = x2 * c + x1 * s
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention — O(L) memory, GQA/MQA aware
# ---------------------------------------------------------------------------


def _gqa_expand(q: jax.Array, n_kv: int) -> jax.Array:
    """(B, L, H, D) → (B, L, K, G, D) grouping query heads per KV head."""
    b, l, h, d = q.shape
    return q.reshape(b, l, n_kv, h // n_kv, d)


def flash_attention(
    q: jax.Array,  # (B, Lq, H, D)
    k: jax.Array,  # (B, Lk, K, D)
    v: jax.Array,  # (B, Lk, K, D)
    *,
    causal: bool = True,
    q_chunk: int = 512,
    kv_chunk: int = 512,
    causal_mode: str = "triangle",  # triangle | masked
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Online-softmax chunked attention in pure jnp.

    ``triangle`` mode iterates only the lower-triangular chunk pairs (a
    static python loop over q chunks with per-chunk-length kv scans), which
    halves causal FLOPs vs ``masked`` mode (full kv scan + mask). Both are
    reverse-mode differentiable. Non-causal attention always scans all kv
    chunks.
    """
    b, lq, h, d = q.shape
    _, lk, n_kv, _ = k.shape
    g = h // n_kv
    scale = 1.0 / jnp.sqrt(jnp.array(d, jnp.float32))

    q_chunk = min(q_chunk, lq)
    kv_chunk = min(kv_chunk, lk)
    pad = -lq % q_chunk
    if pad and causal and bias is None and lq == lk and q_chunk == kv_chunk:
        # Right-pad a ragged causal sequence (a prompt bucket that is not a
        # whole number of chunks) to whole chunks. Causal masking keeps the
        # padded keys out of every real query; the padded rows are dropped.
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        out = flash_attention(
            jnp.pad(q, widths), jnp.pad(k, widths), jnp.pad(v, widths),
            causal=True, q_chunk=q_chunk, kv_chunk=kv_chunk,
            causal_mode=causal_mode,
        )
        return out[:, :lq]
    if lq % q_chunk or lk % kv_chunk:
        raise ValueError(
            f"seq lengths ({lq},{lk}) must divide chunks ({q_chunk},{kv_chunk})"
        )
    nq, nk = lq // q_chunk, lk // kv_chunk

    qg = _gqa_expand(q, n_kv)  # (B, Lq, K, G, D)

    def attend_block(qc, kc, vc, qpos0, kpos0, need_mask):
        """One (q_chunk x kv_chunk) block of scores; qc is (B, K, G, q, D)."""
        s = jnp.einsum(
            "bkgqd,bskd->bkgqs", qc.astype(jnp.float32), kc.astype(jnp.float32)
        ) * scale  # (B, K, G, q, s)
        if need_mask:
            qpos = qpos0 + jnp.arange(qc.shape[-2])
            kpos = kpos0 + jnp.arange(kc.shape[1])
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
        return s

    def scan_kv(qc, k_all, v_all, qpos0, n_kv_chunks, diag_mask_last):
        """Online softmax over the given kv chunks (lax.scan)."""
        kr = k_all[:, : n_kv_chunks * kv_chunk].reshape(
            b, n_kv_chunks, kv_chunk, n_kv, d
        )
        vr = v_all[:, : n_kv_chunks * kv_chunk].reshape(
            b, n_kv_chunks, kv_chunk, n_kv, d
        )
        kr = jnp.moveaxis(kr, 1, 0)  # (n, B, s, K, D)
        vr = jnp.moveaxis(vr, 1, 0)

        q_len = qc.shape[-2]
        m0 = jnp.full((b, n_kv, g, q_len), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, n_kv, g, q_len), jnp.float32)
        acc0 = jnp.zeros((b, n_kv, g, q_len, d), jnp.float32)

        def body(carry, inputs):
            m, l, acc = carry
            idx, kc, vc = inputs
            kpos0 = idx * kv_chunk
            need_mask = causal and (
                diag_mask_last or causal_mode == "masked"
            )
            s = attend_block(qc, kc, vc, qpos0, kpos0, need_mask)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p, vc.astype(jnp.float32))
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        idxs = jnp.arange(n_kv_chunks)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (idxs, kr, vr))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out  # (B, K, G, q, D)

    if not causal or causal_mode == "masked" or nq == 1:
        outs = []
        for i in range(nq):
            # (B, q, K, G, D) → (B, K, G, q, D)
            qc = jnp.moveaxis(qg[:, i * q_chunk : (i + 1) * q_chunk], 1, -2)
            out = scan_kv(qc, k, v, i * q_chunk, nk, diag_mask_last=True)
            outs.append(out)
        o = jnp.concatenate([jnp.moveaxis(x, -2, 1) for x in outs], axis=1)
        return o.reshape(b, lq, h, d).astype(q.dtype)

    # triangle mode: q chunk i attends kv chunks 0..i; only the diagonal
    # block needs the causal mask (assumes q_chunk == kv_chunk alignment).
    if q_chunk != kv_chunk:
        raise ValueError("triangle mode requires q_chunk == kv_chunk")
    outs = []
    for i in range(nq):
        qc = jnp.moveaxis(qg[:, i * q_chunk : (i + 1) * q_chunk], 1, -2)
        if i == 0:
            s = attend_block(
                qc, k[:, :kv_chunk], v[:, :kv_chunk], 0, 0, True
            )
            m = jnp.max(s, axis=-1)
            p = jnp.where(jnp.isfinite(s), jnp.exp(s - m[..., None]), 0.0)
            l = jnp.sum(p, axis=-1)
            acc = jnp.einsum(
                "bkgqs,bskd->bkgqd", p, v[:, :kv_chunk].astype(jnp.float32)
            )
            out = acc / jnp.maximum(l[..., None], 1e-30)
        else:
            # off-diagonal chunks 0..i-1 (no mask) via scan, then diagonal.
            out_nodiag_m_l = _scan_with_final_diag(
                qc, k, v, i, kv_chunk, b, n_kv, g, d, scale
            )
            out = out_nodiag_m_l
        outs.append(out)
    o = jnp.concatenate([jnp.moveaxis(x, -2, 1) for x in outs], axis=1)
    return o.reshape(b, lq, h, d).astype(q.dtype)


def _scan_with_final_diag(qc, k, v, i, chunk, b, n_kv, g, d, scale):
    """Triangle-mode inner loop: chunks 0..i-1 unmasked + masked diagonal."""
    kr = k[:, : i * chunk].reshape(b, i, chunk, n_kv, d)
    vr = v[:, : i * chunk].reshape(b, i, chunk, n_kv, d)
    kr = jnp.moveaxis(kr, 1, 0)
    vr = jnp.moveaxis(vr, 1, 0)
    q_len = qc.shape[-2]

    m0 = jnp.full((b, n_kv, g, q_len), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, n_kv, g, q_len), jnp.float32)
    acc0 = jnp.zeros((b, n_kv, g, q_len, d), jnp.float32)

    def body(carry, inputs):
        m, l, acc = carry
        kc, vc = inputs
        s = (
            jnp.einsum(
                "bkgqd,bskd->bkgqs",
                qc.astype(jnp.float32),
                kc.astype(jnp.float32),
            )
            * scale
        )
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc * corr[..., None] + pv), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kr, vr))

    # masked diagonal block
    kc = k[:, i * chunk : (i + 1) * chunk]
    vc = v[:, i * chunk : (i + 1) * chunk]
    s = (
        jnp.einsum(
            "bkgqd,bskd->bkgqs", qc.astype(jnp.float32), kc.astype(jnp.float32)
        )
        * scale
    )
    qpos = i * chunk + jnp.arange(q_len)
    kpos = i * chunk + jnp.arange(chunk)
    mask = qpos[:, None] >= kpos[None, :]
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_new[..., None]), 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc = acc * corr[..., None] + jnp.einsum(
        "bkgqs,bskd->bkgqd", p, vc.astype(jnp.float32)
    )
    return acc / jnp.maximum(l_new[..., None], 1e-30)


# ---------------------------------------------------------------------------
# Decode attention (single new token against a KV cache)
# ---------------------------------------------------------------------------


def decode_attention(
    q: jax.Array,  # (B, 1, H, D)
    k_cache: jax.Array,  # (B, S, K, D)
    v_cache: jax.Array,  # (B, S, K, D)
    cur_len: jax.Array | int,  # valid cache length (scalar or (B,))
) -> jax.Array:
    """One-step attention over the cache; positions ≥ cur_len are masked."""
    b, s, n_kv, d = k_cache.shape
    h = q.shape[2]
    g = h // n_kv
    scale = 1.0 / jnp.sqrt(jnp.array(d, jnp.float32))

    qg = q.reshape(b, 1, n_kv, g, d)
    scores = (
        jnp.einsum(
            "bqkgd,bskd->bkgqs",
            qg.astype(jnp.float32),
            k_cache.astype(jnp.float32),
        )
        * scale
    )  # (B, K, G, 1, S)
    pos = jnp.arange(s)
    cur = jnp.asarray(cur_len)
    if cur.ndim == 0:
        valid = pos < cur
        scores = jnp.where(valid[None, None, None, None, :], scores, -jnp.inf)
    else:
        valid = pos[None, :] < cur[:, None]  # (B, S)
        scores = jnp.where(valid[:, None, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype)
