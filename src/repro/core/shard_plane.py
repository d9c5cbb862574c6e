"""Sharded control plane — ``shard_map`` row-axis partitioning of the
fused kernels for 10^7+ entitlements.

The single-device tick costs ~154 ms at 1M rows (``BENCH_tick.json``)
and scales linearly in the row count: past a few million entitlements
the row axis is the wall.  This module wraps the SAME kernel bodies in
``shard_map`` over a 1-D device mesh (axis ``"rows"``):

* every per-row quantity (burst EWMA, Eq. 1 weights, debt gap, the
  water-filling want/take vectors) is computed on the device that owns
  the row block — elementwise math shards embarrassingly;
* only the pool-level aggregates the math genuinely couples cross the
  mesh: the protected reserved floor, the water-filling round totals
  (active weight / count / filled), the demand remainder, and the
  admission quantum's per-request row gathers — each an ``all_gather``
  of S scalars (or one psum of one-hot request contributions);
* decisions are BIT-IDENTICAL to the single-device kernels: the row
  reductions in ``control_plane`` use a fixed positional binary tree
  (``tree_sum``/``tree_any``), so per-shard subtrees + the top tree
  over the gathered shard roots reproduce the exact single-device adds
  in the exact same order (see the shard-stable reduction note there).
  ``tests/test_shard_plane.py`` pins single-device == multi-device ==
  scalar oracle on a forced-host CPU mesh
  (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

Admission (``shard_admit_quantum``) splits into the part that scales
with rows and the part that scales with requests: the O(N) work — Eq. 1
weights and the per-request row gathers — runs sharded, then the
inherently sequential O(M) replay runs replicated on a COMPACTED state
(each request's row remapped to a dense id in request space) through
the unmodified ``admit_quantum`` body, so the sequential decision
stream is the same f32 adds in the same order by construction.

Churn stays device-local through ``ShardedResidentStore``
(``core.resident``): per-shard free lists and per-shard device-mirror
blocks mean entitlement add/remove re-uploads one block, not the pool.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.control_plane import (
    TRACE_COUNTS,
    ControlState,
    _tick_impl,
    bucket_width,
    priority_rows,
)
from repro.core.markers import kernel
from repro.core.types import PriorityCoefficients
from repro.core.vectorized import admit_quantum

#: the one mesh axis of the control plane — entitlement rows.
AXIS = "rows"

#: mesh cache: ``Mesh`` is a static jit argument, so every call site
#: must present the SAME object per device count or the dispatch cache
#: fragments (the sanitizer's retrace pass flags inline ``Mesh(...)``
#: construction at shard-kernel call sites for exactly this reason).
_MESH_CACHE: dict[int, Mesh] = {}


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, n).bit_length() - 1)


def row_mesh(n_devices: Optional[int] = None) -> Mesh:
    """The cached 1-D ``rows`` mesh over ``n_devices`` devices (default:
    the largest power of two the backend offers).  Forced-host CPU
    meshes (``XLA_FLAGS=--xla_force_host_platform_device_count=8``)
    come through here exactly like real accelerator meshes."""
    devs = jax.devices()
    if n_devices is None:
        n_devices = _pow2_floor(len(devs))
    if n_devices > len(devs):
        raise ValueError(
            f"row_mesh({n_devices}) exceeds {len(devs)} visible devices")
    if n_devices & (n_devices - 1):
        raise ValueError(f"mesh size must be a power of two, got "
                         f"{n_devices}")
    mesh = _MESH_CACHE.get(n_devices)
    if mesh is None:
        mesh = Mesh(np.array(devs[:n_devices]), (AXIS,))
        _MESH_CACHE[n_devices] = mesh
    return mesh


def shard_width(n_rows: int, mesh: Mesh) -> int:
    """Row pad width for a sharded dispatch: the pow2 bucket_width,
    floored at the mesh size so every device owns an equal (pow2)
    block.  Equal pow2 blocks are what make the tree reductions
    decompose exactly (and what ``shard_map`` requires)."""
    return max(bucket_width(n_rows), mesh.size)


def store_mesh(n_shards: int) -> Mesh:
    """The mesh an ``n_shards`` store places its row blocks on: the
    largest pow2 device count that does not exceed the shard count, so
    device blocks align with free-list shards (size 1 on one chip)."""
    return row_mesh(min(_pow2_floor(len(jax.devices())), n_shards))


def pool_mesh(pool) -> Optional[Mesh]:
    """The mesh a pool's tick/admission should dispatch on, or None to
    stay single-device: requires a ``ShardedResidentStore`` (per-shard
    free lists keep churn device-local) whose placement mesh spans ≥2
    devices."""
    mesh = getattr(pool.store, "mesh", None)
    if mesh is None or mesh.size < 2:
        return None
    return mesh


# -- the sharded tick ---------------------------------------------------------

@kernel(oracle="repro.core.control_plane.control_tick")
@partial(jax.jit, static_argnames=("coeff", "mesh"))
def shard_tick(state: ControlState, capacity_tps: jax.Array,
               measured_tps: jax.Array, used_kv: jax.Array,
               used_conc: jax.Array, demand_tps: jax.Array,
               avg_slo_ms: jax.Array,
               coeff: PriorityCoefficients = PriorityCoefficients(),
               *, mesh: Mesh,
               ) -> tuple[ControlState, jax.Array, jax.Array]:
    """:func:`control_plane.control_tick` under ``shard_map``: row
    arrays split into per-device blocks, pool scalars replicated, the
    shared ``_tick_impl`` body run per block with ``axis_name`` set so
    its tree reductions combine across the mesh.  Row count must be a
    multiple of the mesh size (use :func:`shard_width`).  Output state,
    allocations and weights come back row-sharded; decisions are
    bit-identical to the single-device kernel."""
    TRACE_COUNTS["shard_tick"] += 1            # repro: allow[retrace-hazard] -- trace-time counter: runs only while compiling, counts variants

    def block(s, cap, m, kv, conc, d, slo):
        return _tick_impl(s, cap, m, kv, conc, d, slo, coeff,
                          axis_name=AXIS)

    row, rep = P(AXIS), P()
    return jax.shard_map(
        block, mesh=mesh,
        in_specs=(row, rep, row, row, row, row, rep),
        out_specs=(row, row, row),
        check_vma=False,
    )(state, capacity_tps, measured_tps, used_kv, used_conc,
      demand_tps, avg_slo_ms)


# -- the sharded admission quantum --------------------------------------------

def _one_hot_gather(own, li, col):
    """Gather ``col[li]`` where this shard owns the row, summed across
    shards: exactly one shard contributes each element (the rest add
    zero — exact for f32), so the psum IS the global gather."""
    v = col[li]
    squeeze_bool = v.dtype == jnp.bool_
    if squeeze_bool:
        v = v.astype(jnp.int32)
    out = jax.lax.psum(jnp.where(own, v, jnp.zeros_like(v)), AXIS)
    return out.astype(bool) if squeeze_bool else out


def _gather_block(state, bucket, infl, kv, w_rows, ents):
    """One shard's half of the admission quantum: dense per-request
    gathers of every row quantity the sequential replay reads."""
    idx = jax.lax.axis_index(AXIS)
    n_local = state.class_code.shape[0]
    loc = ents - idx * n_local
    own = (loc >= 0) & (loc < n_local)
    li = jnp.clip(loc, 0, n_local - 1)
    g = partial(_one_hot_gather, own, li)
    return (g(w_rows), g(state.bound), g(state.class_code),
            g(state.baseline_conc), g(state.baseline_kv),
            g(bucket), g(infl), g(kv))


def _gather_compute_block(state, bucket, infl, kv, avg_slo, ents,
                          *, coeff):
    """Gather block that also computes the Eq. 1 weights on the shard
    (elementwise → bitwise equal to the single-device computation)."""
    w_rows = priority_rows(state, avg_slo, coeff)
    return _gather_block(state, bucket, infl, kv, w_rows, ents)


@kernel(oracle="repro.core.vectorized.admit_quantum")
@partial(jax.jit, static_argnames=("coeff", "slack", "mesh"))
def shard_admit_quantum(arr: ControlState,
                        bucket_level: jax.Array,      # f32 [N]
                        in_flight: jax.Array,         # i32 [N]
                        kv_in_use: jax.Array,         # f32 [N]
                        pool_in_flight: jax.Array,    # i32 []
                        pool_conc_cap: jax.Array,     # f32 []
                        running_min_priority: jax.Array,  # f32 []
                        pool_avg_slo: jax.Array,      # f32 []
                        req_ent: jax.Array,           # i32 [M]
                        req_tokens: jax.Array,        # f32 [M]
                        req_kv: jax.Array,            # f32 [M]
                        pool_resident: jax.Array = None,   # i32 []
                        req_live: Optional[jax.Array] = None,  # bool [M]
                        weights: Optional[jax.Array] = None,   # f32 [N]
                        coeff: PriorityCoefficients = PriorityCoefficients(),
                        slack: float = 0.0,
                        *, mesh: Mesh,
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`vectorized.admit_quantum` with the row axis sharded.

    The O(N) half — Eq. 1 weights (when not passed) and the per-request
    row gathers — runs under ``shard_map``; the O(M) sequential replay
    then runs replicated on a request-space COMPACTION of the touched
    rows: ``req_ent`` is remapped to dense ids (``jnp.unique`` over the
    static quantum width), the gathered row state is scattered into
    [M]-wide arrays, and the unmodified :func:`admit_quantum` body
    replays the quantum on them.  Every value the replay reads and
    every f32 update it applies is element-for-element the same as the
    single-device kernel's, in the same order — decisions, deny
    reasons and returned priorities are bit-identical."""
    TRACE_COUNTS["shard_admit_quantum"] += 1   # repro: allow[retrace-hazard] -- trace-time counter: runs only while compiling, counts variants
    n_requests = req_ent.shape[0]
    if pool_resident is None:
        pool_resident = jnp.asarray(pool_conc_cap, jnp.float32)

    row, rep = P(AXIS), P()
    if weights is None:
        gathered = jax.shard_map(
            partial(_gather_compute_block, coeff=coeff), mesh=mesh,
            in_specs=(row, row, row, row, rep, rep),
            out_specs=rep, check_vma=False,
        )(arr, bucket_level, in_flight, kv_in_use, pool_avg_slo, req_ent)
    else:
        gathered = jax.shard_map(
            _gather_block, mesh=mesh,
            in_specs=(row, row, row, row, row, rep),
            out_specs=rep, check_vma=False,
        )(arr, bucket_level, in_flight, kv_in_use, weights, req_ent)
    (req_w, bound_g, class_g, bconc_g, bkv_g,
     bucket_g, infl_g, kv_g) = gathered

    # compact the touched rows into request space: at most M distinct
    # rows appear in a quantum, so the replicated replay never touches
    # an [N] array — its width is the (already padded) quantum width.
    _, inverse = jnp.unique(req_ent, size=n_requests, fill_value=0,
                            return_inverse=True)
    cids = inverse.reshape(n_requests).astype(jnp.int32)

    def scatter(vals, dtype):
        # duplicate ids write identical values — deterministic
        return jnp.zeros((n_requests,), dtype).at[cids].set(
            vals.astype(dtype))

    zeros_f = jnp.zeros((n_requests,), jnp.float32)
    arr_c = ControlState(
        class_code=scatter(class_g, jnp.int32),
        bound=scatter(bound_g, bool),
        baseline_tps=zeros_f,
        baseline_kv=scatter(bkv_g, jnp.float32),
        baseline_conc=scatter(bconc_g, jnp.float32),
        slo_ms=jnp.ones((n_requests,), jnp.float32),
        burst=zeros_f,
        debt=zeros_f,
    )
    return admit_quantum(
        arr_c,
        scatter(bucket_g, jnp.float32),
        scatter(infl_g, jnp.int32),
        scatter(kv_g, jnp.float32),
        pool_in_flight, pool_conc_cap, running_min_priority,
        pool_avg_slo, cids, req_tokens, req_kv,
        pool_resident=pool_resident, req_live=req_live,
        weights=scatter(req_w, jnp.float32),
        coeff=coeff, slack=slack)


# -- the sharded fleet plan ---------------------------------------------------

@kernel(oracle="repro.core.fleet.plan_fleet")
@partial(jax.jit, static_argnames=("config", "mesh"))
def shard_plan_fleet(current: jax.Array, lo: jax.Array, hi: jax.Array,
                     per_tps: jax.Array, per_kv: jax.Array,
                     per_conc: jax.Array, res_tps: jax.Array,
                     res_kv: jax.Array, res_conc: jax.Array,
                     demand_tps: jax.Array, ewma_prev: jax.Array,
                     seeded: jax.Array, low_ticks: jax.Array,
                     config=None,
                     *, mesh: Mesh,
                     ) -> tuple[jax.Array, jax.Array, jax.Array,
                                jax.Array, jax.Array]:
    """:func:`fleet.plan_fleet` with the POOL axis sharded.  The scale
    policy is per-pool elementwise (no cross-pool reduction), so each
    device plans its block independently — trivially bit-identical;
    the rebalancer's cross-pool matching stays host-side."""
    TRACE_COUNTS["shard_plan_fleet"] += 1      # repro: allow[retrace-hazard] -- trace-time counter: runs only while compiling, counts variants
    # deferred: fleet → autoscaler → pool → shard_plane would cycle at
    # module import time; resolved once per trace, never per dispatch
    from repro.core.fleet import FleetPlannerConfig, plan_fleet
    if config is None:
        config = FleetPlannerConfig()

    def block(c, l, h, pt, pk, pc, rt, rk, rc, d, e, s, lt):
        return plan_fleet(c, l, h, pt, pk, pc, rt, rk, rc, d, e, s, lt,
                          config=config)

    row = P(AXIS)
    return jax.shard_map(
        block, mesh=mesh,
        in_specs=tuple([row] * 13),
        out_specs=tuple([row] * 5),
        check_vma=False,
    )(current, lo, hi, per_tps, per_kv, per_conc,
      res_tps, res_kv, res_conc, demand_tps, ewma_prev, seeded,
      low_ticks)
