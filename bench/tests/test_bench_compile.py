"""Compile yi-6b-8l's serving programs for a described TPU v5e.

The slot decode of the short pool and its largest prefill bucket, at the
configuration's real widths, must compile for one v5e chip and fit its
16 GiB by ``memory_analysis()``. No chip is needed; the topology is
described inside a fixture, so only the worker that runs this file loads
the TPU compiler.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

CONFIG = Path(__file__).resolve().parents[1] / "configs" / "yi-6b-8l.json"
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def serving(one_chip):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.configs.base import ArchConfig
    from repro.models import Model

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    cfg = json.loads(CONFIG.read_text())
    m = {k: v for k, v in cfg["model"].items() if k != "dtype"}
    model = Model(ArchConfig(name=cfg["name"], **m))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        model.abstract())
    short = next(p for p in cfg["pools"] if p["name"] == "short")
    return model, params, short


def _bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def test_slot_decode_fits_one_v5e(serving, one_chip):
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import build_slot_decode
    from repro.serving.kv_cache import slot_batch_axes, slot_cell

    model, params, short = serving
    c_max, slots = short["c_max"], short["slots"]
    cache = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        model.cache_specs(slot_cell(c_max, slots)))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    decode = build_slot_decode(model, slot_batch_axes(model, c_max, slots))
    compiled = decode.lower(params, cache, vec, vec).compile()
    assert _bytes(compiled) < HBM_BYTES


def test_largest_prefill_bucket_fits_one_v5e(serving, one_chip):
    import jax
    import jax.numpy as jnp

    model, params, short = serving
    batch = {
        "tokens": jax.ShapeDtypeStruct((1, short["c_max"]), jnp.int32,
                                       sharding=one_chip),
        "last_pos": jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip),
    }
    compiled = jax.jit(model.prefill).lower(params, batch).compile()
    assert _bytes(compiled) < HBM_BYTES
