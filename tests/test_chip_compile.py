"""Compile the control-plane kernels at real widths for a TPU v5e that
is described, not attached.

The TPU compiler installed with JAX compiles for a described topology,
so these tests refuse — at no chip time — a kernel the chip's compiler
would refuse: an unsupported op, an unpartitionable ``shard_map``, a
program that does not fit a chip's memory.  Nothing runs, so they say
nothing about results or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every test
worker imports every test file.  Where it cannot be described, the
fixture skips.  The persistent compilation cache is off around these
compiles (an entry written for a described chip cannot be read back
without one).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.control_plane import (
    ControlState,
    control_tick,
    control_tick_pools,
)
from repro.core.fleet import FleetPlannerConfig, plan_fleet
from repro.core.shard_plane import AXIS, shard_admit_quantum, shard_tick
from repro.core.vectorized import admit_quantum, owner_min

#: ControlState field dtypes (class code, bound flag, then f32 columns)
_STATE_DTYPES = {"class_code": jnp.int32, "bound": jnp.bool_}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as exc:                  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:4]), (AXIS,))


def spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def state_spec(shape, sharding) -> ControlState:
    return ControlState(**{
        name: spec(shape, _STATE_DTYPES.get(name, jnp.float32), sharding)
        for name in ControlState.__dataclass_fields__})


#: HBM of one TPU v5e chip
V5E_HBM_BYTES = 16 * 10**9


def compiled_for_tpu(lowered):
    """Compile for the described chip; the program (arguments, outputs
    and scratch, per chip) must fit one chip's HBM."""
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, used
    return compiled


def test_control_tick_2p20(one_chip):
    n = 1 << 20
    f32 = lambda: spec((n,), jnp.float32, one_chip)          # noqa: E731
    scalar = spec((), jnp.float32, one_chip)
    compiled_for_tpu(control_tick.lower(
        state_spec((n,), one_chip), scalar, f32(), f32(), f32(), f32(),
        scalar))


def test_control_tick_pools_8x2p17(one_chip):
    shape = (8, 1 << 17)
    f32 = lambda: spec(shape, jnp.float32, one_chip)         # noqa: E731
    per_pool = spec((8,), jnp.float32, one_chip)
    compiled_for_tpu(control_tick_pools.lower(
        state_spec(shape, one_chip), per_pool, f32(), f32(), f32(), f32(),
        per_pool))


def admit_args(n, m, row, rep):
    """``admit_quantum`` arguments exactly as the gateway passes them:
    rows of width ``n`` with sharding ``row``, ``m`` requests and the
    pool scalars with sharding ``rep``."""
    return dict(
        arr=state_spec((n,), row),
        bucket_level=spec((n,), jnp.float32, row),
        in_flight=spec((n,), jnp.int32, row),
        kv_in_use=spec((n,), jnp.float32, row),
        pool_in_flight=spec((), jnp.int32, rep),
        pool_conc_cap=spec((), jnp.float32, rep),
        running_min_priority=spec((), jnp.float32, rep),
        pool_avg_slo=spec((), jnp.float32, rep),
        req_ent=spec((m,), jnp.int32, rep),
        req_tokens=spec((m,), jnp.float32, rep),
        req_kv=spec((m,), jnp.float32, rep),
        pool_resident=spec((), jnp.int32, rep),
        req_live=spec((m,), jnp.bool_, rep),
        weights=spec((n,), jnp.float32, row))


def test_admit_quantum_2p17_x_10240(one_chip):
    compiled_for_tpu(admit_quantum.lower(
        **admit_args(1 << 17, 10240, one_chip, one_chip)))


def test_plan_fleet_512_pools(one_chip):
    p = 512
    i32 = lambda: spec((p,), jnp.int32, one_chip)            # noqa: E731
    f32 = lambda: spec((p,), jnp.float32, one_chip)          # noqa: E731
    compiled_for_tpu(plan_fleet.lower(
        i32(), i32(), i32(), f32(), f32(), f32(), f32(), f32(), f32(),
        f32(), f32(), spec((p,), jnp.bool_, one_chip), i32(),
        config=FleetPlannerConfig()))


def test_shard_tick_2p22_on_four_chips(mesh4):
    n = 1 << 22
    row, rep = NamedSharding(mesh4, P(AXIS)), NamedSharding(mesh4, P())
    f32 = lambda: spec((n,), jnp.float32, row)               # noqa: E731
    scalar = spec((), jnp.float32, rep)
    compiled = compiled_for_tpu(shard_tick.lower(
        state_spec((n,), row), scalar, f32(), f32(), f32(), f32(), scalar,
        mesh=mesh4))
    # only pool aggregates cross chips: the tick needs collectives
    assert "all-reduce" in compiled.as_text() \
        or "all-gather" in compiled.as_text()


def test_shard_admit_quantum_2p22_on_four_chips(mesh4):
    row, rep = NamedSharding(mesh4, P(AXIS)), NamedSharding(mesh4, P())
    compiled = compiled_for_tpu(shard_admit_quantum.lower(
        **admit_args(1 << 22, 10240, row, rep), mesh=mesh4))
    assert "all-reduce" in compiled.as_text()


def test_owner_min_2p17(one_chip):
    n = 1 << 17
    compiled_for_tpu(owner_min.lower(
        spec((n,), jnp.float32, one_chip), spec((n,), jnp.bool_, one_chip)))


def test_owner_min_2p22_on_four_chips(mesh4):
    """The sharded store uploads the owner mask ``P("rows")`` beside
    the weights: one reduction across the chips."""
    n = 1 << 22
    row = NamedSharding(mesh4, P(AXIS))
    compiled = compiled_for_tpu(owner_min.lower(
        spec((n,), jnp.float32, row), spec((n,), jnp.bool_, row)))
    assert "all-reduce" in compiled.as_text()
