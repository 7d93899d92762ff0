"""Model building blocks: jnp flash attention, RoPE/M-RoPE, SSD, xLSTM."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.layers import (
    apply_rope,
    decode_attention,
    flash_attention,
    mrope_angles,
    rms_norm,
    rope_angles,
)
from repro.models.ssm import ssd_chunked, ssd_step
from repro.models.xlstm import mlstm_chunked, mlstm_step, slstm_scan


def naive_attention(q, k, v, causal=True):
    B, L, H, D = q.shape
    K = k.shape[2]
    g = H // K
    qg = q.reshape(B, L, K, g, D).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.float32(D))
    if causal:
        mask = jnp.tril(jnp.ones((L, L), bool))
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, L, H, D)


@pytest.mark.parametrize("mode", ["triangle", "masked"])
@pytest.mark.parametrize("H,K", [(8, 2), (4, 1), (4, 4)])
def test_flash_attention_value_and_grad(mode, H, K):
    rng = np.random.default_rng(0)
    B, L, D = 2, 192, 32
    q = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, L, K, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, L, K, D)), jnp.float32)
    out = flash_attention(q, k, v, q_chunk=64, kv_chunk=64, causal_mode=mode)
    expect = naive_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)

    g = jax.grad(
        lambda q: flash_attention(
            q, k, v, q_chunk=64, kv_chunk=64, causal_mode=mode
        ).sum()
    )(q)
    g_ref = jax.grad(lambda q: naive_attention(q, k, v).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=2e-5)


@pytest.mark.parametrize("mode", ["triangle", "masked"])
def test_flash_attention_ragged_causal_length(mode):
    """A causal length that is not a whole number of chunks is padded."""
    rng = np.random.default_rng(2)
    B, L, H, K, D = 1, 200, 4, 1, 32
    q = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, L, K, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, L, K, D)), jnp.float32)
    out = flash_attention(q, k, v, q_chunk=64, kv_chunk=64, causal_mode=mode)
    assert out.shape == (B, L, H, D)
    expect = naive_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


def test_decode_attention_per_batch_lengths():
    rng = np.random.default_rng(1)
    B, S, H, K, D = 3, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)
    lens = jnp.asarray([10, 33, 64])
    out = decode_attention(q, kc, vc, lens)
    for b in range(B):
        n = int(lens[b])
        exp = naive_attention(
            q[b : b + 1], kc[b : b + 1, :n], vc[b : b + 1, :n], causal=False
        )
        np.testing.assert_allclose(
            np.asarray(out[b]), np.asarray(exp[0]), atol=1e-5
        )


def test_rope_rotation_preserves_norm():
    pos = jnp.arange(16)[None]
    cos, sin = rope_angles(pos, 64, 10_000.0)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 16, 2, 64)), jnp.float32)
    y = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1),
        rtol=1e-5,
    )


def test_rope_relative_property():
    """<rope(q,m), rope(k,n)> depends only on m−n."""
    D = 32
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(D,)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(D,)), jnp.float32)

    def dot_at(m, n):
        cos_m, sin_m = rope_angles(jnp.array([[m]]), D, 10_000.0)
        cos_n, sin_n = rope_angles(jnp.array([[n]]), D, 10_000.0)
        qm = apply_rope(q[None, None, None], cos_m, sin_m)[0, 0, 0]
        kn = apply_rope(k[None, None, None], cos_n, sin_n)[0, 0, 0]
        return float(jnp.dot(qm, kn))

    assert dot_at(5, 3) == pytest.approx(dot_at(105, 103), abs=1e-4)
    assert dot_at(7, 0) == pytest.approx(dot_at(57, 50), abs=1e-4)


def test_mrope_equals_rope_on_equal_streams():
    pos3 = jnp.broadcast_to(jnp.arange(16)[None, None], (3, 2, 16))
    c3, s3 = mrope_angles(pos3, 64, 10_000.0, (8, 12, 12))
    c1, s1 = rope_angles(jnp.broadcast_to(jnp.arange(16)[None], (2, 16)), 64, 10_000.0)
    np.testing.assert_allclose(np.asarray(c3), np.asarray(c1))
    np.testing.assert_allclose(np.asarray(s3), np.asarray(s1))


def test_mrope_sections_validate():
    with pytest.raises(ValueError):
        mrope_angles(jnp.zeros((3, 1, 4)), 64, 1e4, (8, 8, 8))


def test_rms_norm_basic():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 16)), jnp.float32)
    w = jnp.zeros((16,), jnp.float32)
    y = rms_norm(x, w)
    rms = np.sqrt(np.mean(np.asarray(y) ** 2, axis=-1))
    np.testing.assert_allclose(rms, 1.0, rtol=1e-4)


@pytest.mark.parametrize("chunk", [1, 16, 64])
def test_ssd_chunked_equals_stepwise(chunk):
    rng = np.random.default_rng(3)
    B, L, H, P, G, N = 2, 64, 4, 8, 1, 16
    x = jnp.asarray(rng.normal(size=(B, L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, L, H)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(B, L, G, N)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(B, L, G, N)), jnp.float32)
    y, s = ssd_chunked(x, dt, a, bm, cm, chunk=chunk)
    st = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(L):
        yy, st = ssd_step(x[:, t], dt[:, t], a, bm[:, t], cm[:, t], st)
        ys.append(yy)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(jnp.stack(ys, 1)), atol=1e-4
    )
    np.testing.assert_allclose(np.asarray(s), np.asarray(st), atol=1e-4)


@pytest.mark.parametrize("chunk", [8, 32, 64])
def test_mlstm_chunked_equals_stepwise(chunk):
    rng = np.random.default_rng(4)
    B, L, H, Dk, Dv = 2, 64, 4, 16, 16
    q = jnp.asarray(rng.normal(size=(B, L, H, Dk)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, L, H, Dk)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, L, H, Dv)), jnp.float32)
    ip = jnp.asarray(rng.normal(size=(B, L, H)), jnp.float32)
    fp = jnp.asarray(rng.normal(size=(B, L, H)) + 2.0, jnp.float32)
    h, (cf, nf) = mlstm_chunked(q, k, v, ip, fp, chunk=chunk)
    c = jnp.zeros((B, H, Dv, Dk))
    n = jnp.zeros((B, H, Dk))
    hs = []
    for t in range(L):
        ht, (c, n) = mlstm_step(q[:, t], k[:, t], v[:, t], ip[:, t], fp[:, t], (c, n))
        hs.append(ht)
    np.testing.assert_allclose(
        np.asarray(h), np.asarray(jnp.stack(hs, 1)), atol=2e-5
    )
    np.testing.assert_allclose(np.asarray(cf), np.asarray(c), atol=2e-5)


def test_slstm_stability_extreme_gates():
    """Stabilizer keeps sLSTM finite under extreme gate preactivations."""
    B, L, H, D = 1, 32, 2, 4
    big = jnp.full((B, L, H, D), 30.0)
    r = jnp.zeros((H, D, D))
    h, state = slstm_scan(big, big, -big, big, r, r, r, r)
    assert np.isfinite(np.asarray(h)).all()
    h2, _ = slstm_scan(-big, -big, big, -big, r, r, r, r)
    assert np.isfinite(np.asarray(h2)).all()
