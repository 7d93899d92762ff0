"""Compile the two accelerator paths for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler is handed abstract shapes
on one device of a described ``v5e:2x2`` topology and must accept them.
That catches what the chip's compiler refuses (unsupported operations,
programs that do not fit the device) on every test run.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers each
import this file. Keep these tests in this one file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

GIB = 1 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # A compile for a described device is written to the persistent cache
    # but cannot be read back without the chip, so keep the cache off.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree,
    )


def _des_spec(n: int):
    """The static spec of the two-pool Azure fleet of the 1k benchmark."""
    from benchmarks.sim_throughput import RATE_PER_10K, build_pools
    from repro.sim import A100_LLAMA3_70B, FleetSim, jax_engine
    from repro.traces import TraceSpec, generate_trace_columns

    rate = max(50.0, RATE_PER_10K * n / 10_000)
    cols = generate_trace_columns(
        TraceSpec(trace="azure", num_requests=n, rate=rate, seed=42)
    )
    pools, _ = build_pools(cols, rate, 2)
    fleet = FleetSim(pools, A100_LLAMA3_70B, backend="jax", spillover=False)
    return jax_engine._fleet_spec(fleet, cols)[0]


@pytest.mark.parametrize("grid,g", [(False, 0), (True, 16)],
                         ids=["single_lane", "grid_g16"])
def test_des_runner_compiles_for_v5e(one_chip, grid, g):
    from repro.sim import jax_engine

    n = 1000
    spec = _des_spec(n)
    with jax.enable_x64():
        fn = jax_engine._runner(spec, n, grid)
        args = _on(jax_engine._abstract_inputs(spec, n, grid, g), one_chip)
        compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < GIB


@pytest.fixture(scope="module")
def gemma(one_chip):
    from repro.configs import get_config
    from repro.models import Model

    model = Model(get_config("gemma-2b"))
    return model, _on(model.abstract(), one_chip)


def test_gemma_2b_prefill_compiles_for_v5e(one_chip, gemma):
    model, params = gemma
    batch = _on(
        {
            "tokens": jax.ShapeDtypeStruct((1, 512), jnp.int32),
            "last_pos": jax.ShapeDtypeStruct((1,), jnp.int32),
        },
        one_chip,
    )
    compiled = jax.jit(model.prefill).lower(params, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16 * GIB


def test_gemma_2b_slot_decode_compiles_for_v5e(one_chip, gemma):
    from repro.serving.engine import build_slot_decode
    from repro.serving.kv_cache import slot_batch_axes, slot_cell

    model, params = gemma
    c_max, slots = 2048, 16
    decode = build_slot_decode(model, slot_batch_axes(model, c_max, slots))
    state = _on(model.cache_specs(slot_cell(c_max, slots)), one_chip)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    compiled = decode.lower(params, state, vec, vec).compile()
    mem = compiled.memory_analysis()
    weights = sum(
        int(np.prod(p.shape)) * p.dtype.itemsize
        for p in jax.tree.leaves(params)
    )
    assert mem.argument_size_in_bytes >= weights
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * GIB
