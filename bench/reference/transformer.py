"""Plain float32 forward pass of a dense decoder, and its fp8 control.

Written from the published Llama-style description (Yi-6B: RMSNorm,
rotary embeddings with the half-split rotation, grouped-query attention,
SwiGLU MLP, untied output head), in ``jax.numpy`` with every matrix
product at ``Precision.HIGHEST``, one layer at a time and attention in
blocks of query rows so that a long sequence fits. It imports nothing of
the program. One departure from the published form: RMSNorm weights are
stored as offsets from one (``x * (1 + w)``), the parametrisation the
served tree uses; the benchmark draws them as zeros, so the two agree.

``quant="fp8"`` is the control: every matrix product takes float8 (e4m3)
inputs, weights scaled per matrix and activations per row, accumulated in
float32, which is the precision step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BLOCK = 512  # query rows per attention block; sequences pad to a multiple
E4M3_MAX = 448.0


def _fp8(x, axis):
    """Round ``x`` through float8 e4m3 with an amax scale over ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, w, quant, a_axis, w_axis):
    if quant == "fp8":
        a, w = _fp8(a, a_axis), _fp8(w, w_axis)
    return jnp.einsum(spec, a, w, precision=HI)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0
                          / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv  # (T, half)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(x, blocks, i, m, quant):
    """One decoder layer over the whole (padded) sequence ``x`` (T, d)."""
    p = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
        .astype(jnp.float32), blocks)
    m = dict(m)
    t, eps = x.shape[0], m["norm_eps"]
    h_, kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    g = h_ // kv
    pos = jnp.arange(t)

    h = _rms(x, p["attn_norm"], eps)
    q = _mm("td,dhk->thk", h, p["w_q"], quant, -1, 0)
    k = _mm("td,dhk->thk", h, p["w_k"], quant, -1, 0)
    v = _mm("td,dhk->thk", h, p["w_v"], quant, -1, 0)
    q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    q = q.reshape(t, kv, g, hd)
    outs = []
    for lo in range(0, t, BLOCK):
        hi = lo + BLOCK
        qb = q[lo:hi]
        s = _mm("qkgd,tkd->kgqt", qb, k[:hi], quant, -1, -1) / np.sqrt(hd)
        mask = pos[lo:hi, None] >= pos[None, :hi]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        outs.append(_mm("kgqt,tkd->qkgd", a, v[:hi], quant, -1, 0))
    o = jnp.concatenate(outs, axis=0).reshape(t, h_, hd)
    x = x + _mm("thk,hkd->td", o, p["w_o"], quant, (-2, -1), (0, 1))

    h = _rms(x, p["mlp_norm"], eps)
    gate = _mm("td,df->tf", h, p["w_gate"], quant, -1, 0)
    up = _mm("td,df->tf", h, p["w_up"], quant, -1, 0)
    act = jax.nn.silu(gate) * up
    return x + _mm("tf,fd->td", act, p["w_down"], quant, -1, 0)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(x, w, eps):
    return _rms(x, w.astype(jnp.float32), eps)


def hidden(params, m: dict, tokens, quant: str = "none"):
    """Final-normed hidden states (T_pad, d) of ``tokens``, padded with id 0
    to a whole number of attention blocks (causal, so padding changes no
    real row)."""
    n = len(tokens)
    t = -(-n // BLOCK) * BLOCK
    ids = np.zeros(t, np.int32)
    ids[:n] = tokens
    emb = params["embed"].astype(jnp.float32)
    if quant == "fp8":
        emb = _fp8(emb, -1)
    x = jnp.take(emb, jnp.asarray(ids), axis=0)
    key = tuple(sorted((k, v) for k, v in m.items()
                       if isinstance(v, (int, float))))
    for i in range(m["n_layers"]):
        x = _layer(x, params["blocks"], i, key, quant)
    return _final(x, params["final_norm"], m["norm_eps"])


@functools.partial(jax.jit, static_argnames=("quant",))
def _head_rows(h, lm_head, quant):
    w = lm_head.astype(jnp.float32)
    return _mm("td,dv->tv", h, w, quant, -1, 0)


def logit_gaps(params, m: dict, prompt, served, quant: str = "none",
               rows: int = 256) -> np.ndarray:
    """Per served token, how far the reference's logit of the chosen token
    lies below the reference's best logit at that position.

    With ``quant="none"`` the chosen tokens are ``served``, the program's.
    With ``quant="fp8"`` they are the tokens the fp8 control puts first at
    each position of the same sequence."""
    seq = list(prompt) + list(served[:-1])
    first = len(prompt) - 1
    ref = hidden(params, m, seq)[first:first + len(served)]
    ctl = (hidden(params, m, seq, quant)[first:first + len(served)]
           if quant != "none" else None)
    served = np.asarray(served)
    gaps = []
    for lo in range(0, len(served), rows):
        r = _head_rows(ref[lo:lo + rows], params["lm_head"], "none")
        if ctl is None:
            chosen = jnp.asarray(served[lo:lo + rows])
        else:
            chosen = jnp.argmax(
                _head_rows(ctl[lo:lo + rows], params["lm_head"], quant), -1)
        best = jnp.max(r, axis=-1)
        got = jnp.take_along_axis(r, chosen[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(best - got))
    return np.concatenate(gaps) if gaps else np.zeros(0)
