"""Unified attention-transformer assembly (dense / moe / vlm / audio).

One definition covers gemma-2b, granite-3-8b, yi-6b, granite-34b,
llama4-scout, llama4-maverick (alternating dense/MoE), qwen2-vl (M-RoPE,
embedding frontend), musicgen (cross-attention + codebook heads),
llama3-70b and qwen3-235b.

Layer stacks are ``lax.scan``'d over stacked parameters (one scan step =
``moe_every`` consecutive layers so alternating patterns stay scannable),
with optional activation rematerialization in train mode.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain
from repro.models import heads as heads_lib
from repro.models.layers import (
    apply_rope,
    decode_attention,
    flash_attention,
    mlp,
    mrope_angles,
    rms_norm,
    rope_angles,
)
from repro.models.moe import (
    DECODE_CAPACITY_FACTOR,
    PREFILL_CAPACITY_FACTOR,
    TRAIN_CAPACITY_FACTOR,
    moe_layer,
    moe_param_defs,
)
from repro.models.params import ParamDef, stack_tree

# ---------------------------------------------------------------------------
# Parameter declarations
# ---------------------------------------------------------------------------


def attention_defs(cfg: ArchConfig, *, cross: bool = False) -> dict:
    h, k, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    prefix = "cross_" if cross else ""
    # The default normal(0.02) init, not "scaled": that one reads the
    # second-to-last axis as fan-in, a head axis here. With one KV head it
    # drew w_k at std 1, a random model's attention scores ran into the
    # hundreds, and softmax became an argmax that rounding noise flips.
    return {
        f"{prefix}attn_norm": ParamDef(
            (d,), ("embed",), init="zeros", dtype=jnp.float32
        ),
        f"{prefix}w_q": ParamDef((d, h, dh), ("embed", "heads", "head_dim")),
        f"{prefix}w_k": ParamDef((d, k, dh), ("embed", "kv_heads", "head_dim")),
        f"{prefix}w_v": ParamDef((d, k, dh), ("embed", "kv_heads", "head_dim")),
        f"{prefix}w_o": ParamDef((h, dh, d), ("heads", "head_dim", "embed")),
    }


def mlp_defs(cfg: ArchConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    defs = {
        "mlp_norm": ParamDef((d,), ("embed",), init="zeros", dtype=jnp.float32),
        "w_up": ParamDef((d, f), ("embed", "ffn"), init="scaled"),
        "w_down": ParamDef((f, d), ("ffn", "embed"), init="scaled"),
    }
    if cfg.activation in ("swiglu", "geglu"):
        defs["w_gate"] = ParamDef((d, f), ("embed", "ffn"), init="scaled")
    return defs


def dense_layer_defs(cfg: ArchConfig) -> dict:
    defs = {**attention_defs(cfg), **mlp_defs(cfg)}
    if cfg.cross_attention:
        defs.update(attention_defs(cfg, cross=True))
    return defs


def moe_layer_defs(cfg: ArchConfig) -> dict:
    defs = {
        **attention_defs(cfg),
        "mlp_norm": ParamDef(
            (cfg.d_model,), ("embed",), init="zeros", dtype=jnp.float32
        ),
        "moe": moe_param_defs(
            cfg.d_model,
            cfg.moe_d_ff or cfg.d_ff,
            cfg.n_experts,
            cfg.n_shared_experts,
            cfg.activation,
        ),
    }
    if cfg.cross_attention:
        defs.update(attention_defs(cfg, cross=True))
    return defs


def transformer_defs(cfg: ArchConfig) -> dict:
    """Full parameter tree for an attention-family architecture."""
    d, v = cfg.d_model, cfg.padded_vocab
    defs: dict[str, Any] = {}
    if cfg.frontend == "tokens":
        defs["embed"] = ParamDef((v, d), ("vocab", "embed"), init="normal")
    if cfg.is_moe:
        if cfg.moe_every not in (1, 2):
            raise ValueError("moe_every must be 1 or 2")
        n_steps = cfg.n_layers // cfg.moe_every
        step: dict[str, Any] = {"moe_block": moe_layer_defs(cfg)}
        if cfg.moe_every == 2:
            step["dense_block"] = dense_layer_defs(cfg)
        defs["blocks"] = stack_tree(step, n_steps)
    else:
        defs["blocks"] = stack_tree(dense_layer_defs(cfg), cfg.n_layers)
    defs["final_norm"] = ParamDef((d,), ("embed",), init="zeros", dtype=jnp.float32)
    if cfg.n_codebooks > 0:
        defs["codebook_heads"] = ParamDef(
            (cfg.n_codebooks, d, v), ("codebooks", "embed", "vocab"), init="scaled"
        )
    elif not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, v), ("embed", "vocab"), init="scaled")
    return defs


# ---------------------------------------------------------------------------
# Sublayer application
# ---------------------------------------------------------------------------


def _project_qkv(x, p, prefix=""):
    q = jnp.einsum("bld,dhk->blhk", x, p[f"{prefix}w_q"])
    k = jnp.einsum("bld,dhk->blhk", x, p[f"{prefix}w_k"])
    v = jnp.einsum("bld,dhk->blhk", x, p[f"{prefix}w_v"])
    return q, k, v


def quantize_kv(t: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(position, head) symmetric int8 KV quantization.

    Halves decode HBM traffic and doubles slot concurrency (beyond-paper
    §Perf iteration; composes with the paper's pool right-sizing by raising
    ρ — see EXPERIMENTS.md). Scale shape (B, S, K, 1) fp16.
    """
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(
        jnp.round(t.astype(jnp.float32) / scale), -127, 127
    ).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(
        jnp.bfloat16
    )


def _self_attention_full(
    x, p, cos, sin, cfg: ArchConfig, causal_mode: str, kv_dtype: str = "bf16"
):
    """Train/prefill self-attention over the whole sequence."""
    xn = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q, k, v = _project_qkv(xn, p)
    if cos is not None:
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    q = constrain(q, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "kv_heads", None))
    o = flash_attention(
        q, k, v, causal=True, causal_mode=causal_mode,
        q_chunk=min(512, q.shape[1]), kv_chunk=min(512, k.shape[1]),
    )
    out = jnp.einsum("blhk,hkd->bld", o, p["w_o"])
    if kv_dtype == "int8":
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return x + out, (kq, vq, ks, vs)
    return x + out, (k, v)


def _self_attention_decode(
    x, p, cos, sin, cfg: ArchConfig, cache, index, kv_dtype: str = "bf16"
):
    """Single-token decode; cache (k, v[, k_scale, v_scale])."""
    xn = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q, k, v = _project_qkv(xn, p)
    if cos is not None:
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    if kv_dtype == "int8":
        k_cache, v_cache, k_scale, v_scale = cache
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        k_cache = jax.lax.dynamic_update_slice(k_cache, kq, (0, index, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, vq, (0, index, 0, 0))
        k_scale = jax.lax.dynamic_update_slice(
            k_scale, ks.astype(k_scale.dtype), (0, index, 0, 0)
        )
        v_scale = jax.lax.dynamic_update_slice(
            v_scale, vs.astype(v_scale.dtype), (0, index, 0, 0)
        )
        o = decode_attention(
            q,
            dequantize_kv(k_cache, k_scale),
            dequantize_kv(v_cache, v_scale),
            index + 1,
        )
        new_cache = (k_cache, v_cache, k_scale, v_scale)
    else:
        k_cache, v_cache = cache
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, index, 0, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, index, 0, 0)
        )
        o = decode_attention(q, k_cache, v_cache, index + 1)
        new_cache = (k_cache, v_cache)
    out = jnp.einsum("blhk,hkd->bld", o, p["w_o"])
    return x + out, new_cache


def _cross_attention(x, p, memory_kv, cfg: ArchConfig):
    """Encoder-memory cross attention (musicgen text conditioning)."""
    mk, mv = memory_kv
    xn = rms_norm(x, p["cross_attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bld,dhk->blhk", xn, p["cross_w_q"])
    o = flash_attention(
        q, mk, mv, causal=False,
        q_chunk=min(512, q.shape[1]), kv_chunk=min(512, mk.shape[1]),
    )
    return x + jnp.einsum("blhk,hkd->bld", o, p["cross_w_o"])


def _memory_kv(p, memory):
    mk = jnp.einsum("bmd,dhk->bmhk", memory, p["cross_w_k"])
    mv = jnp.einsum("bmd,dhk->bmhk", memory, p["cross_w_v"])
    return mk, mv


def _mlp_sublayer(x, p, cfg: ArchConfig):
    xn = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    return x + mlp(xn, p, cfg.activation)


def _moe_sublayer(x, p, cfg: ArchConfig, group_size: int, capacity_factor: float):
    xn = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    out, aux = moe_layer(
        xn,
        p["moe"],
        n_experts=cfg.n_experts,
        top_k=cfg.top_k,
        activation=cfg.activation,
        group_size=group_size,
        capacity_factor=capacity_factor,
    )
    return x + out, aux


def _block_apply(
    x,
    p,
    cfg: ArchConfig,
    cos,
    sin,
    *,
    mode: str,  # full | decode
    is_moe_block: bool,
    memory=None,
    cache=None,
    index=None,
    causal_mode: str = "triangle",
    moe_group: int = 512,
    moe_cf: float = TRAIN_CAPACITY_FACTOR,
    kv_dtype: str = "bf16",
):
    """One (sub-)layer: self-attn [+cross] + (mlp | moe). Returns
    (x, new_cache, aux_loss)."""
    n_self = 4 if kv_dtype == "int8" else 2
    if mode == "full":
        x, kv = _self_attention_full(
            x, p, cos, sin, cfg, causal_mode, kv_dtype
        )
        new_cache = kv
    else:
        x, new_cache = _self_attention_decode(
            x, p, cos, sin, cfg, cache[:n_self], index, kv_dtype
        )
    if cfg.cross_attention:
        if mode == "full":
            mkv = _memory_kv(p, memory)
            new_cache = (*new_cache, *mkv)
        else:
            mkv = cache[n_self:]
            new_cache = (*new_cache, *mkv)
        x = _cross_attention(x, p, mkv, cfg)
    aux = jnp.zeros((), jnp.float32)
    if is_moe_block:
        x, aux = _moe_sublayer(x, p, cfg, moe_group, moe_cf)
    else:
        x = _mlp_sublayer(x, p, cfg)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Whole-model forward passes
# ---------------------------------------------------------------------------


def _positions_full(batch, cfg: ArchConfig, length: int):
    if cfg.pos_type == "none":
        return None, None
    if cfg.pos_type == "mrope":
        pos = batch["positions"]  # (3, B, L)
        return mrope_angles(pos, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections)
    bsz = (
        batch["tokens"].shape[0]
        if "tokens" in batch
        else batch["embeds"].shape[0]
    )
    pos = jnp.broadcast_to(jnp.arange(length)[None], (bsz, length))
    return rope_angles(pos, cfg.head_dim, cfg.rope_theta)


def _embed_input(params, cfg: ArchConfig, batch) -> jax.Array:
    if cfg.frontend == "tokens":
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
        if cfg.tie_embeddings:  # gemma-style sqrt(d) scaling
            x = x * jnp.sqrt(jnp.array(cfg.d_model, x.dtype))
    else:
        x = batch["embeds"]
    return constrain(x, ("batch", None, "embed"))


def _scan_blocks(
    params,
    cfg: ArchConfig,
    x,
    cos,
    sin,
    *,
    mode: str,
    memory=None,
    caches=None,
    index=None,
    remat: str = "none",
    causal_mode: str = "triangle",
    moe_group: int = 512,
    moe_cf: float = TRAIN_CAPACITY_FACTOR,
    kv_dtype: str = "bf16",
):
    """Scan over the stacked layer blocks. Returns (x, new_caches, aux)."""

    def step(carry, xs):
        h, aux_acc = carry
        p_step, cache_step = xs

        def run(h):
            aux_step = jnp.zeros((), jnp.float32)
            new_caches = {}
            if cfg.is_moe:
                if cfg.moe_every == 2:
                    h2, nc, a = _block_apply(
                        h, p_step["dense_block"], cfg, cos, sin, mode=mode,
                        is_moe_block=False, memory=memory,
                        cache=None if cache_step is None else cache_step["dense_block"],
                        index=index, causal_mode=causal_mode, moe_group=moe_group,
                        moe_cf=moe_cf, kv_dtype=kv_dtype,
                    )
                    new_caches["dense_block"] = nc
                    aux_step = aux_step + a
                else:
                    h2 = h
                h2, nc, a = _block_apply(
                    h2, p_step["moe_block"], cfg, cos, sin, mode=mode,
                    is_moe_block=True, memory=memory,
                    cache=None if cache_step is None else cache_step["moe_block"],
                    index=index, causal_mode=causal_mode, moe_group=moe_group,
                    moe_cf=moe_cf, kv_dtype=kv_dtype,
                )
                new_caches["moe_block"] = nc
                aux_step = aux_step + a
            else:
                h2, nc, a = _block_apply(
                    h, p_step, cfg, cos, sin, mode=mode,
                    is_moe_block=False, memory=memory, cache=cache_step,
                    index=index, causal_mode=causal_mode, moe_group=moe_group,
                    moe_cf=moe_cf, kv_dtype=kv_dtype,
                )
                new_caches = nc
                aux_step = aux_step + a
            return h2, new_caches, aux_step

        if remat == "full":
            run = jax.checkpoint(
                run, policy=jax.checkpoint_policies.nothing_saveable
            )
        elif remat == "dots":
            run = jax.checkpoint(
                run,
                policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            )
        h2, new_caches, aux_step = run(h)
        return (h2, aux_acc + aux_step), new_caches

    xs = (params["blocks"], caches)
    (x, aux), new_caches = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)), xs)
    return x, new_caches, aux


def _head(params, cfg: ArchConfig, x):
    vv = cfg.vocab if cfg.padded_vocab != cfg.vocab else None
    if cfg.n_codebooks > 0:
        return heads_lib.codebook_logits(
            x, params["codebook_heads"], valid_vocab=vv
        )
    if cfg.tie_embeddings:
        return heads_lib.lm_logits(x, params["embed"], tied=True, valid_vocab=vv)
    return heads_lib.lm_logits(x, params["lm_head"], valid_vocab=vv)


def forward(
    params,
    cfg: ArchConfig,
    batch: dict,
    *,
    remat: str = "none",
    causal_mode: str = "triangle",
    moe_group: int = 512,
) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward → (logits, aux_loss). Train/eval mode."""
    x = _embed_input(params, cfg, batch)
    length = x.shape[1]
    cos, sin = _positions_full(batch, cfg, length)
    memory = batch.get("memory")
    x, _, aux = _scan_blocks(
        params, cfg, x, cos, sin, mode="full", memory=memory,
        remat=remat, causal_mode=causal_mode, moe_group=moe_group,
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head(params, cfg, x)
    return logits, aux


def loss_fn(
    params,
    cfg: ArchConfig,
    batch: dict,
    *,
    remat: str = "none",
    aux_coeff: float = 0.01,
    causal_mode: str = "triangle",
    moe_group: int = 512,
) -> tuple[jax.Array, dict]:
    logits, aux = forward(
        params, cfg, batch, remat=remat, causal_mode=causal_mode,
        moe_group=moe_group,
    )
    loss, metrics = heads_lib.softmax_xent(logits, batch["labels"])
    total = loss + aux_coeff * aux
    metrics["aux_loss"] = aux
    metrics["total_loss"] = total
    return total, metrics


def prefill(
    params,
    cfg: ArchConfig,
    batch: dict,
    *,
    causal_mode: str = "triangle",
    moe_group: int = 512,
    kv_dtype: str = "bf16",
) -> tuple[jax.Array, Any]:
    """Prefill pass → (last-position logits, kv caches)."""
    x = _embed_input(params, cfg, batch)
    length = x.shape[1]
    cos, sin = _positions_full(batch, cfg, length)
    memory = batch.get("memory")
    x, caches, _ = _scan_blocks(
        params, cfg, x, cos, sin, mode="full", memory=memory,
        causal_mode=causal_mode, moe_group=moe_group,
        moe_cf=PREFILL_CAPACITY_FACTOR, kv_dtype=kv_dtype,
    )
    # "last_pos" supports right-padded prompts (serving buckets): logits are
    # taken at the true last prompt token, not the padded end.
    if "last_pos" in batch:
        x = jax.vmap(
            lambda h, p: jax.lax.dynamic_slice_in_dim(h, p, 1, axis=0)
        )(x, batch["last_pos"])
    else:
        x = x[:, -1:]
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head(params, cfg, x)
    return logits[:, 0], caches


def decode_step(
    params,
    cfg: ArchConfig,
    caches: Any,
    batch: dict,
    *,
    moe_group: int = 512,
    kv_dtype: str = "bf16",
) -> tuple[jax.Array, Any]:
    """One decode iteration. ``batch["index"]`` is the write position;
    caches are (k, v[, cross_k, cross_v]) stacked over scan steps."""
    x = _embed_input(params, cfg, batch)
    index = batch["index"]
    if cfg.pos_type == "none":
        cos = sin = None
    elif cfg.pos_type == "mrope":
        cos, sin = mrope_angles(
            batch["positions"], cfg.head_dim, cfg.rope_theta, cfg.mrope_sections
        )
    else:
        bsz = x.shape[0]
        pos = jnp.broadcast_to(
            jnp.asarray(index)[None, None], (bsz, 1)
        )
        cos, sin = rope_angles(pos, cfg.head_dim, cfg.rope_theta)
    x, new_caches, _ = _scan_blocks(
        params, cfg, x, cos, sin, mode="decode", caches=caches, index=index,
        moe_group=moe_group, moe_cf=DECODE_CAPACITY_FACTOR, kv_dtype=kv_dtype,
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head(params, cfg, x)
    return logits[:, 0], new_caches
