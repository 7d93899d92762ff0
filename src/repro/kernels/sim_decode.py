"""Fused DES decode-advance pass: the compiled tier's hot inner kernel.

One pool round of the jax DES backend (:mod:`repro.sim.jax_engine`)
spends most of its time in a dense per-instance pass over the
``(instances, n_seq)`` slot arrays: pick the oldest prefilling sequence
and feed it one chunk, compute the event-distance k-jump (completion /
truncation / time-limit, with the KV-growth over-check), advance decode
state, and stage the completion/truncation records for the scatter that
follows.

:func:`decode_advance_jnp` is that pass in plain ``jnp`` over the full
``(I, S)`` arrays, the one implementation on every backend. It is
bit-identical to the NumPy engine's ``VectorPoolSim._round`` by
construction (same formulas, same IEEE-754 op order, float64 event
times). XLA emulates float64 on TPU in plain jnp code; a Pallas kernel
cannot take float64 operands there, so a kernel form of this pass needs
an integer event clock first.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.pools import KV_BLOCK_TOKENS

#: Sentinels for "no constraint" in masked min-reductions (int32-safe).
_BIG_I = 1 << 30
_BIG_F = 1.0e18


def _blocks_for(tok):
    return jnp.maximum(1, (tok + (KV_BLOCK_TOKENS - 1)) // KV_BLOCK_TOKENS)


def decode_advance_jnp(
    t_limit,  # scalar f64 — sweep boundary (next arrival / inf)
    busy,  # (I,) bool — due instances with active sequences
    now,  # (I,) f64 — per-instance wake time (0 where not busy)
    nact,  # (I,) i32 — active sequences per instance
    free,  # (I,) i32 — free KV blocks per instance
    occ,  # (I, S) bool — slot occupied
    pre,  # (I, S) i32 — prefill tokens remaining
    sq,  # (I, S) i32 — admission sequence number (age tie-break)
    inp,  # (I, S) i32 — input tokens
    gen,  # (I, S) i32 — generated tokens
    rem,  # (I, S) i32 — output tokens remaining
    blk,  # (I, S) i32 — KV blocks held
    ft,  # (I, S) f64 — first-token time (nan = not yet)
    tr,  # (I, S) bool — truncated flag
    *,
    w: float,
    h: float,
    chunk: int,
    c_max: int,
):
    """One fused decode-advance over the full slot arrays.

    Identical formulas and op order to ``VectorPoolSim._round``'s
    k-jump/advance section; every float op is float64. Returns a dict:
    ``pre`` (post-chunk prefill), ``dec`` (decoding mask), ``k``/``end``
    (jump length and end-of-round time per instance), advanced
    ``gen``/``rem``/``ft``/``tr``, ``trunc_new`` (this-round truncation
    mask) and ``comp`` (completion mask) for the record scatter.
    """
    f64 = jnp.float64
    i32 = jnp.int32
    I, _ = occ.shape
    t_it = w + h * nact.astype(f64)
    bb = busy[:, None]

    # one prefill chunk to the oldest prefilling sequence
    pmask = occ & (pre > 0)
    has_pre = pmask.any(axis=1) & busy
    oldest = jnp.argmin(jnp.where(pmask, sq, _BIG_I), axis=1)
    # One-hot select/subtract instead of a row gather + scatter:
    # XLA:CPU expands even a one-update-per-row scatter into a serial
    # while loop; the masked eltwise form fuses away (identical integer
    # arithmetic — the one-hot row sum selects exactly one slot).
    oh = jnp.arange(occ.shape[1])[None, :] == oldest[:, None]
    take = jnp.minimum(
        jnp.sum(jnp.where(oh, pre, 0), axis=1, dtype=i32), chunk
    )
    pre_arr = pre - jnp.where(oh & has_pre[:, None], take[:, None], 0)

    # event-distance k-jump (identical formulas to the host round)
    dec = occ & (pre_arr == 0) & (rem > 0)
    ctx0 = inp + gen
    k_complete = jnp.min(jnp.where(dec, rem, _BIG_I), axis=1)
    k_trunc = jnp.min(jnp.where(dec, c_max - ctx0, _BIG_I), axis=1)
    q = (t_limit - now) / t_it
    k_time = jnp.where(jnp.isfinite(q), jnp.ceil(q - 1e-9), _BIG_F)
    k = jnp.minimum(jnp.minimum(k_complete, k_trunc).astype(f64), k_time)
    k = jnp.where(has_pre, 1.0, jnp.maximum(k, 1.0))
    k = jnp.minimum(k, float(_BIG_I)).astype(i32)

    def growth(kk):
        ng = gen + jnp.where(dec, kk[:, None], 0)
        nd = jnp.where(occ, _blocks_for(inp + ng), 0)
        return jnp.maximum(nd - blk, 0).sum(axis=1, dtype=i32)

    over = busy & (growth(k) > free)
    k = jnp.where(over, 1, k)
    end = now + k.astype(f64) * t_it

    # advance + stage completion/truncation for the record scatter
    kcol = jnp.where(dec, k[:, None], 0)
    gen_a = gen + kcol
    rem_a = rem - kcol
    ft_a = jnp.where(dec & jnp.isnan(ft), (now + t_it)[:, None], ft)
    trunc_n = dec & (inp + gen_a >= c_max) & (rem_a > 0) & bb
    rem_a = jnp.where(trunc_n, 0, rem_a)
    tr_a = tr | trunc_n
    comp = dec & (rem_a == 0) & bb
    return {
        "pre": pre_arr,
        "dec": dec,
        "k": k,
        "end": end,
        "gen": gen_a,
        "rem": rem_a,
        "ft": ft_a,
        "trunc_new": trunc_n,
        "tr": tr_a,
        "comp": comp,
    }

