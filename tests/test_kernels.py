"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, paged_attention, ref, ssd_scan

F32 = jnp.float32
BF16 = jnp.bfloat16


def rand(rng, shape, dtype, scale=1.0):
    return (jnp.asarray(rng.normal(size=shape)) * scale).astype(dtype)


FLASH_CASES = [
    # (B, L, H, K, D, dtype, tol)
    (2, 256, 8, 2, 64, F32, 2e-5),
    (1, 512, 4, 1, 128, F32, 2e-5),  # MQA
    (2, 128, 4, 4, 32, F32, 2e-5),  # MHA
    (1, 256, 8, 8, 256, F32, 2e-5),  # gemma-style head_dim
    (2, 256, 8, 2, 64, BF16, 2e-2),
    (1, 384, 6, 2, 64, F32, 2e-5),  # non-pow2 length (divides 128)
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_oracle(case, causal):
    B, L, H, K, D, dtype, tol = case
    rng = np.random.default_rng(0)
    q = rand(rng, (B, L, H, D), dtype)
    k = rand(rng, (B, L, K, D), dtype)
    v = rand(rng, (B, L, K, D), dtype)
    out = flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=128, interpret=True
    )
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=tol
    )


PAGED_CASES = [
    # (B, H, K, D, page, pages_per_seq, dtype, tol)
    (4, 8, 2, 64, 16, 8, F32, 2e-5),
    (2, 8, 1, 128, 16, 4, F32, 2e-5),  # MQA
    (3, 4, 4, 32, 32, 4, F32, 2e-5),
    (4, 8, 2, 64, 16, 8, BF16, 2e-2),
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_attention_matches_oracle(case):
    B, H, K, D, page, pps, dtype, tol = case
    rng = np.random.default_rng(1)
    total_pages = B * pps * 2
    q = rand(rng, (B, H, D), dtype)
    kp = rand(rng, (total_pages, page, K, D), dtype)
    vp = rand(rng, (total_pages, page, K, D), dtype)
    perm = rng.permutation(total_pages)[: B * pps]
    bt = jnp.asarray(perm.reshape(B, pps), jnp.int32)
    lengths = jnp.asarray(
        rng.integers(1, pps * page + 1, size=(B,)), jnp.int32
    )
    out = paged_attention(q, kp, vp, bt, lengths, interpret=True)
    expect = ref.paged_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=tol
    )


def test_paged_attention_ignores_unmapped_pages():
    """Pages past `lengths` must not affect the output (poison test)."""
    rng = np.random.default_rng(2)
    B, H, K, D, page, pps = 2, 4, 2, 64, 16, 4
    q = rand(rng, (B, H, D), F32)
    kp = rand(rng, (16, page, K, D), F32)
    vp = rand(rng, (16, page, K, D), F32)
    bt = jnp.asarray(rng.permutation(16)[: B * pps].reshape(B, pps), jnp.int32)
    lengths = jnp.asarray([20, 35], jnp.int32)
    base = paged_attention(q, kp, vp, bt, lengths, interpret=True)
    # poison every page beyond each sequence's length
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    for b in range(B):
        first_dead = int(np.ceil(lengths[b] / page))
        for j in range(first_dead, pps):
            kp2[int(bt[b, j])] = 1e9
            vp2[int(bt[b, j])] = 1e9
    out = paged_attention(
        q, jnp.asarray(kp2), jnp.asarray(vp2), bt, lengths, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(base), atol=1e-4
    )


SSD_CASES = [
    # (B, L, H, P, N, chunk, dtype, tol)
    (2, 128, 4, 32, 16, 32, F32, 5e-5),
    (1, 256, 2, 64, 64, 128, F32, 1e-4),
    (2, 64, 8, 16, 32, 64, F32, 5e-5),
    (2, 128, 4, 32, 16, 32, BF16, 6e-2),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_matches_oracle(case):
    B, L, H, P, N, chunk, dtype, tol = case
    rng = np.random.default_rng(3)
    x = rand(rng, (B, L, H, P), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, L, H))).astype(F32)
    a = -jnp.asarray(rng.uniform(0.5, 2.0, (H,))).astype(F32)
    bm = rand(rng, (B, L, N), dtype)
    cm = rand(rng, (B, L, N), dtype)
    y, s = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    y_ref, s_ref = ref.ssd_scan_ref(x, dt, a, bm, cm)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), atol=tol
    )
    np.testing.assert_allclose(
        np.asarray(s), np.asarray(s_ref), atol=max(tol, 1e-4)
    )


def test_ssd_scan_state_streams_across_chunks():
    """Final state equals the sequential recurrence regardless of chunking."""
    rng = np.random.default_rng(4)
    B, L, H, P, N = 1, 96, 2, 16, 8
    x = rand(rng, (B, L, H, P), F32)
    dt = jnp.asarray(rng.uniform(0.05, 0.1, (B, L, H))).astype(F32)
    a = -jnp.ones((H,), F32)
    bm = rand(rng, (B, L, N), F32)
    cm = rand(rng, (B, L, N), F32)
    states = []
    for chunk in (32, 48, 96):
        _, s = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
        states.append(np.asarray(s))
    np.testing.assert_allclose(states[0], states[1], atol=1e-4)
    np.testing.assert_allclose(states[0], states[2], atol=1e-4)


def test_paged_attention_int8_pages():
    """int8 KV pages + per-(pos,head) scales ≈ the fp32 oracle (§Perf A1)."""
    rng = np.random.default_rng(5)
    B, H, K, D, page, pps = 3, 8, 2, 64, 16, 4
    total = 16
    q = rand(rng, (B, H, D), F32)
    kp = rand(rng, (total, page, K, D), F32)
    vp = rand(rng, (total, page, K, D), F32)
    bt = jnp.asarray(rng.permutation(total)[: B * pps].reshape(B, pps), jnp.int32)
    lengths = jnp.asarray([64, 40, 13], jnp.int32)

    def quant(t):
        amax = jnp.max(jnp.abs(t), axis=-1, keepdims=True)
        s = jnp.maximum(amax, 1e-6) / 127.0
        qv = jnp.clip(jnp.round(t / s), -127, 127).astype(jnp.int8)
        return qv, s.astype(jnp.float32)

    kq, ks = quant(kp)
    vq, vs = quant(vp)
    out = paged_attention(q, kq, vq, bt, lengths, ks, vs, interpret=True)
    expect = ref.paged_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), atol=5e-2
    )
    # and well inside the quantization-noise envelope
    assert float(jnp.abs(out - expect).max()) < 0.05


# ---------------------------------------------------------------------------
# sim_decode: the DES decode-advance kernel vs its jnp twin
# ---------------------------------------------------------------------------


def _decode_state(seed, I=3, S=8):
    """Random but invariant-respecting slot state (float64 exact class)."""
    rng = np.random.default_rng(seed)
    occ = rng.random((I, S)) < 0.7
    pre = np.where(
        occ & (rng.random((I, S)) < 0.3),
        rng.integers(1, 600, (I, S)),
        0,
    ).astype(np.int32)
    inp = np.where(occ, rng.integers(16, 1200, (I, S)), 0).astype(np.int32)
    gen = np.where(occ & (pre == 0), rng.integers(0, 48, (I, S)), 0).astype(
        np.int32
    )
    rem = np.where(occ, rng.integers(1, 120, (I, S)), 0).astype(np.int32)
    blk = np.where(occ, (inp + gen) // 16 + 1, 0).astype(np.int32)
    sq = rng.permutation(I * S).reshape(I, S).astype(np.int32)
    nact = occ.sum(axis=1, dtype=np.int32)
    busy = nact > 0
    now = np.where(busy, rng.uniform(0.5, 2.0, I), 0.0)
    free = rng.integers(0, 64, I).astype(np.int32)
    ft = np.where(
        occ & (gen > 0), rng.uniform(0.1, 1.0, (I, S)), np.nan
    )
    tr = np.zeros((I, S), bool)
    t_limit = float(now.max() + 0.75)
    return dict(
        t_limit=t_limit, busy=busy, now=now, nact=nact, free=free,
        occ=occ, pre=pre, sq=sq, inp=inp, gen=gen, rem=rem, blk=blk,
        ft=ft, tr=tr,
    )


def test_sim_decode_idle_instances_are_inert():
    """Idle (not busy) instances complete and truncate nothing — the
    busy-gated outputs the engine consumes unmasked must stay silent
    (raw ``gen``/``rem`` are busy-masked by the engine itself)."""
    from repro.kernels.sim_decode import decode_advance_jnp

    s = _decode_state(3)
    s["busy"] = np.zeros_like(s["busy"])
    s["now"] = np.zeros_like(s["now"])
    with jax.enable_x64():
        out = decode_advance_jnp(
            s["t_limit"], s["busy"], s["now"], s["nact"], s["free"],
            s["occ"], s["pre"], s["sq"], s["inp"], s["gen"], s["rem"],
            s["blk"], s["ft"], s["tr"],
            w=2**-10, h=2**-13, chunk=512, c_max=2048,
        )
    assert not np.asarray(out["comp"]).any()
    assert not np.asarray(out["trunc_new"]).any()
