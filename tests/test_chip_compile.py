"""Compile the main path's device programs for a DESCRIBED v5e chip.

No chip is attached: the TPU compiler installed here compiles for a
``v5e:2x2`` topology description and refuses what the chip would refuse
(unsupported ops in a kernel, programs that do not fit HBM). Nothing runs.
The topology is described in a module fixture, never at import: only one
process may load libtpu, and every xdist worker imports this file. The
installed libtpu is required: a topology that cannot be described fails the
test rather than skipping it. The
compilation cache is off around each compile (a TPU entry written here
could not be read back without a chip). The chip's numeric mode is 32-bit,
so x64 is off inside each test.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from apmbackend_tpu import pipeline

CAPACITY = 8192
POD_SERVICES = 10_240
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_mode():
    """32-bit like the chip, no persistent cache around the compile."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def demo_engine(capacity):
    """(cfg, state shapes, params shapes) at config.py's defaults:
    samplesPerBucket 128, lags 360 and 8640, f32. Nothing is allocated."""
    box = {}

    def build():
        cfg, state, params = pipeline.make_demo_engine(
            capacity, 128, [(360, 20.0, 0.1), (8640, 15.0, 0.0)])
        box["cfg"] = cfg
        return state, params

    state, params = jax.eval_shape(build)
    return box["cfg"], state, params


def placed(tree, sharding):
    """Shape-only copies of ``tree``'s leaves, placed on ``sharding``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def total_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_fused_engine_tick_fits_one_chip(one_chip, chip_mode):
    cfg, state, params = demo_engine(CAPACITY)
    state, params = placed((state, params), one_chip)
    label = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(pipeline.engine_tick, static_argnums=1).lower(
        state, cfg, label, params).compile()
    assert total_bytes(compiled) < HBM_BYTES


def test_staged_programs_compile(one_chip, chip_mode, monkeypatch):
    """The programs make_engine_step and RebuildScheduler build on the TPU
    branch (make_staged_executor's stages around engine_core_tick): the
    host-native percentile and rebuild stages are CPU-only, so their gates
    are steered off here."""
    from apmbackend_tpu.ops import stats as dstats

    monkeypatch.setattr(pipeline, "_use_native_percentiles", lambda cfg: False)
    monkeypatch.setattr(pipeline, "default_native_rebuild_gate", lambda cfg: False)
    cfg, state, params = demo_engine(CAPACITY)
    state, params = placed((state, params), one_chip)
    assert pipeline.make_engine_step(cfg).kind == "staged"
    sched = pipeline.RebuildScheduler(cfg)
    assert not sched._native
    label = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    sliding = pipeline.sliding_lag_indices(cfg)
    rings = tuple(state.zscores[i].values for i in sliding)
    cursors = tuple(state.zscores[i].pos for i in sliding)
    evict, write = pipeline.staged_ring_programs()

    jax.jit(dstats.advance_one, static_argnums=1).lower(state.stats, cfg.stats, label).compile()
    evict.lower(rings, cursors).compile()
    evicted = placed(jax.eval_shape(evict, rings, cursors), one_chip)
    core = jax.jit(pipeline.engine_core_tick, static_argnums=1).lower(
        state, cfg, label, params, evicted)
    core.compile()
    *_outs, state2, pushes = placed(core.out_info, one_chip)
    rings2 = tuple(state2.zscores[i].values for i in sliding)
    write.lower(rings2, pushes, tuple(state2.zscores[i].pos for i in sliding)).compile()
    sched._slice_fn.lower(state, cfg, label, sched.chunk).compile()


def test_select_ranks_compiles(one_chip, chip_mode):
    from apmbackend_tpu.ops.pallas_kernels import select_ranks

    window = jax.ShapeDtypeStruct((CAPACITY, 3840), jnp.float32, sharding=one_chip)
    ranks = jax.ShapeDtypeStruct((CAPACITY, 2), jnp.int32, sharding=one_chip)
    compiled = select_ranks.lower(window, ranks).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_sharded_step_compiles_on_2x2(topo, chip_mode):
    from jax.sharding import Mesh

    from apmbackend_tpu.parallel.mesh import SERVICE_AXIS
    from apmbackend_tpu.parallel.sharded import (
        _params_specs,
        _state_specs,
        fused_sharded_program,
        local_config,
    )

    mesh = Mesh(np.array(topo.devices), (SERVICE_AXIS,))
    cfg, state, params = demo_engine(POD_SERVICES)
    program = fused_sharded_program(mesh, cfg, local_config(cfg, len(topo.devices)))

    def shard(tree, specs):
        return jax.tree.map(
            lambda spec, x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                 sharding=NamedSharding(mesh, spec)),
            specs, tree, is_leaf=lambda s: isinstance(s, P))

    state = shard(state, _state_specs(cfg))
    params = shard(params, _params_specs(cfg))
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    compiled = program.lower(state, scalar, params, scalar).compile()
    assert total_bytes(compiled) < HBM_BYTES  # per device
