"""Per-architecture smoke tests (reduced configs) + serving-path parity.

Every assigned arch: instantiate the REDUCED config, run one forward and
one train step on CPU, assert output shapes and no NaNs. Then check
prefill→decode parity (exact for non-MoE; decode==prefill for MoE, whose
capacity semantics legitimately differ from train mode).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, PAPER_MODELS, get_config
from repro.models import Model
from repro.training import TrainConfig, init_train_state, make_train_step

ARCH_IDS = [c.name for c in ASSIGNED]


def make_batch(cfg, B=2, L=32, *, train=True, seed=0):
    key = jax.random.key(seed)
    batch = {}
    if cfg.frontend == "tokens":
        batch["tokens"] = jax.random.randint(key, (B, L), 0, cfg.vocab)
    else:
        batch["embeds"] = (
            jax.random.normal(key, (B, L, cfg.d_model), jnp.float32) * 0.1
        ).astype(jnp.bfloat16)
    if cfg.pos_type == "mrope":
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(L)[None, None], (3, B, L)
        ).astype(jnp.int32)
    if cfg.cross_attention:
        batch["memory"] = (
            jax.random.normal(key, (B, cfg.cross_mem_len, cfg.d_model)) * 0.1
        ).astype(jnp.bfloat16)
    if train:
        if cfg.n_codebooks > 0:
            batch["labels"] = jnp.zeros((B, L, cfg.n_codebooks), jnp.int32)
        else:
            batch["labels"] = jnp.zeros((B, L), jnp.int32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch).reduced()
    model = Model(cfg, remat="full")
    B, L = 2, 32
    batch = make_batch(cfg, B, L)

    logits, aux = model.forward(model.init(jax.random.key(0)), batch)
    if cfg.n_codebooks > 0:
        assert logits.shape == (B, L, cfg.n_codebooks, cfg.padded_vocab)
    else:
        assert logits.shape == (B, L, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())

    tcfg = TrainConfig(total_steps=3, warmup_steps=1)
    train_step, _ = make_train_step(model, tcfg)
    params, opt_state = init_train_state(model, tcfg, jax.random.key(1))
    new_params, _, metrics = jax.jit(train_step)(
        params, opt_state, batch, jnp.int32(0)
    )
    assert np.isfinite(float(metrics["loss"]))
    # parameters actually moved
    moved = any(
        not jnp.array_equal(a, b)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params))
    )
    assert moved


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_matches_forward_last_position(arch):
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, 2, 32, train=False)
    logits_full, _ = model.forward(params, batch)
    logits_pre, cache = model.prefill(params, batch)
    if cfg.is_moe:
        # capacity factors differ between train fwd and serving prefill;
        # parity is checked decode-vs-prefill below instead.
        return
    np.testing.assert_allclose(
        np.asarray(logits_pre, np.float32),
        np.asarray(logits_full[:, -1], np.float32),
        atol=1e-3,
    )
    assert cache is not None


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_consistent_with_prefill(arch):
    """prefill(x[:L]) then decode == prefill(x[:L+1]) last logits."""
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    B, L = 2, 24
    full = make_batch(cfg, B, L, train=False, seed=2)

    # prefill over the full L tokens → reference last logits
    ref_logits, _ = model.prefill(params, full)

    # prefill L-1, pad caches to L, decode token L-1
    part = dict(full)
    if cfg.frontend == "tokens":
        part["tokens"] = full["tokens"][:, : L - 1]
    else:
        part["embeds"] = full["embeds"][:, : L - 1]
    if cfg.pos_type == "mrope":
        part["positions"] = full["positions"][:, :, : L - 1]
    _, cache = model.prefill(params, part)

    def pad(leaf):
        if (
            leaf.ndim == 5
            and leaf.shape[-1] == cfg.head_dim
            and leaf.shape[-2] == cfg.n_kv_heads
            and leaf.shape[-3] == L - 1
        ):
            pads = [(0, 0)] * leaf.ndim
            pads[-3] = (0, 1)
            return jnp.pad(leaf, pads)
        return leaf

    cache = jax.tree.map(pad, cache)
    dec = {"index": jnp.int32(L - 1)}
    if cfg.frontend == "tokens":
        dec["tokens"] = full["tokens"][:, L - 1 :]
    else:
        dec["embeds"] = full["embeds"][:, L - 1 :]
    if cfg.pos_type == "mrope":
        dec["positions"] = full["positions"][:, :, L - 1 :]
    dec_logits, _ = model.decode_step(params, cache, dec)
    np.testing.assert_allclose(
        np.asarray(dec_logits, np.float32),
        np.asarray(ref_logits, np.float32),
        atol=2e-2,
    )


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_cache_specs_structure(arch):
    """cache_specs / cache_axes / init_cache agree structurally."""
    from repro.configs.base import ShapeCell

    cfg = get_config(arch).reduced()
    model = Model(cfg)
    cell = ShapeCell("t", "decode", 64, 2)
    specs = model.cache_specs(cell)
    axes = model.cache_axes(cell)
    assert jax.tree.structure(specs) == jax.tree.structure(
        axes,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(a, (str, type(None))) for a in x),
    )
    for leaf, ax in zip(
        jax.tree.leaves(specs),
        jax.tree.leaves(
            axes,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(a, (str, type(None))) for a in x),
        ),
    ):
        assert len(leaf.shape) == len(ax)


@pytest.mark.parametrize("arch", [c.name for c in PAPER_MODELS])
def test_paper_model_configs_instantiate(arch):
    cfg = get_config(arch)
    model = Model(cfg.reduced())
    batch = make_batch(cfg.reduced(), 1, 16)
    loss, metrics = model.loss(model.init(jax.random.key(0)), batch)
    assert np.isfinite(float(loss))


def test_full_config_param_counts():
    """Full (unreduced) parameter counts are in the published ballpark."""
    expected = {
        "gemma-2b": (2.0e9, 3.5e9),
        "granite-3-8b": (7.5e9, 9.0e9),
        "yi-6b": (5.5e9, 6.5e9),
        "granite-34b": (30e9, 36e9),
        "llama4-scout-17b-a16e": (90e9, 115e9),
        "llama4-maverick-400b-a17b": (380e9, 420e9),
        "qwen2-vl-7b": (6.5e9, 8.5e9),
        "musicgen-medium": (1.3e9, 2.3e9),
        "zamba2-2.7b": (2.3e9, 3.2e9),
        "xlstm-350m": (0.3e9, 0.5e9),
    }
    for name, (lo, hi) in expected.items():
        model = Model(get_config(name))
        n = model.param_count()
        assert lo <= n <= hi, f"{name}: {n/1e9:.2f}B outside [{lo/1e9}, {hi/1e9}]"


def test_moe_active_params():
    m = Model(get_config("llama4-maverick-400b-a17b"))
    assert m.active_param_count() < 0.1 * m.param_count()


@pytest.mark.parametrize("arch", ["gemma-2b", "yi-6b"])
def test_random_attention_scores_stay_soft(arch):
    """At published width, random-init attention scores stay O(1).

    Saturated scores make softmax an argmax, and then any rounding
    difference between two correct code paths (prefill vs forward,
    decode vs forward) flips the output; decode-vs-forward checks on
    random weights lose all power."""
    from repro.models.params import init_params
    from repro.models.transformer import attention_defs

    cfg = get_config(arch)
    p = init_params(attention_defs(cfg), jax.random.key(0))
    # unit-RMS rows, as the attention sees them after rms_norm
    x = jax.random.normal(jax.random.key(1), (64, cfg.d_model), jnp.float32)
    q = jnp.einsum("ld,dk->lk", x, p["w_q"][:, 0].astype(jnp.float32))
    k = jnp.einsum("ld,dk->lk", x, p["w_k"][:, 0].astype(jnp.float32))
    scores = q @ k.T / np.sqrt(cfg.head_dim)
    assert float(jnp.std(scores)) < 4.0
