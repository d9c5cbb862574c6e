"""Mixture-of-Experts MLP (qwen3-style: top-k routing over E experts,
softmax gate, renormalised top-k probabilities).

Dispatch is sort-based and static-shape (TPU-friendly):

  1. router logits → top-k (gates, expert ids) per token;
  2. flatten (T·k) assignments, stable-sort by expert id;
  3. rank-within-expert via exclusive-cumsum of expert counts; tokens
     ranked beyond the per-expert capacity C are dropped (their gate
     contribution is zero — the residual path carries them, standard
     capacity-factor semantics);
  4. scatter into a dense (E, C, d) buffer → batched expert einsum
     (E,C,d)×(E,d,f) — FLOPs ≈ k·cf·T·d·f·(3 matmuls), i.e. within
     capacity_factor of the model FLOPs (no dense-dispatch waste);
  5. gather-combine back to (T, d) with gate weighting.

Distribution: ``moe_mlp`` is the shard-local compute.  Under a mesh it
runs inside ``shard_map`` with experts sharded over the EP axes (data,
and pod when present) and the expert ffn dim sharded over the TP axis:

  tokens (T_loc, d) —all_to_all(EP)→ local experts' slots
  → expert einsum (f sharded over TP, partial down-proj psum over TP)
  —all_to_all(EP)→ back to source shard → local combine.

This is the canonical MoE EP schedule; its all-to-all bytes are what
§Roofline measures for the qwen3 cells.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


def init_moe(key, cfg, dtype) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    E, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    return {
        "router": dense_init(k1, (d, E), jnp.float32),
        "w_gate": dense_init(k2, (E, d, f), dtype),
        "w_up": dense_init(k3, (E, d, f), dtype),
        "w_down": dense_init(k4, (E, f, d), dtype),
    }


def route(router_w: jax.Array, x: jax.Array, cfg
          ) -> tuple[jax.Array, jax.Array]:
    """x (T,d) → (gates (T,k) f32, expert ids (T,k) i32)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(
            jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, idx.astype(jnp.int32)


def _dispatch_indices(expert_ids: jax.Array, E: int, C: int
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sort-based dispatch bookkeeping.

    expert_ids: (N,) flattened token→expert assignments.
    Returns (perm, dst_slot, keep): ``perm`` sorts assignments by
    expert; ``dst_slot`` is the (E·C)-buffer slot for each *sorted*
    assignment; ``keep`` masks assignments within capacity.
    """
    N = expert_ids.shape[0]
    perm = jnp.argsort(expert_ids, stable=True)
    sorted_e = expert_ids[perm]
    counts = jnp.bincount(expert_ids, length=E)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(N, dtype=jnp.int32) - offsets[sorted_e].astype(jnp.int32)
    keep = rank < C
    dst = sorted_e * C + jnp.minimum(rank, C - 1)
    return perm, dst, keep


def moe_mlp(params: dict, x: jax.Array, cfg,
            capacity: int | None = None) -> jax.Array:
    """Shard-local MoE MLP: x (T, d) → (T, d).  SwiGLU experts."""
    T, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    if capacity is None:
        capacity = max(1, int(T * k / E * cfg.moe_capacity_factor))
    gates, idx = route(params["router"], x, cfg)

    flat_e = idx.reshape(T * k)
    flat_g = gates.reshape(T * k)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    perm, dst, keep = _dispatch_indices(flat_e, E, capacity)
    src_tok = flat_t[perm]
    src_gate = jnp.where(keep, flat_g[perm], 0.0)

    # scatter tokens into the (E·C, d) dispatch buffer (dropped → no-op
    # add of zeros)
    buf = jnp.zeros((E * capacity, d), x.dtype)
    vals = jnp.where(keep[:, None], x[src_tok], 0)
    buf = buf.at[dst].add(vals, mode="drop")
    disp = buf.reshape(E, capacity, d)

    g = jnp.einsum("ecd,edf->ecf", disp, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", disp, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    y = y.reshape(E * capacity, d)

    # combine: each kept assignment contributes gate · y[slot]
    contrib = y[dst] * src_gate[:, None].astype(y.dtype)
    out = jnp.zeros((T, d), y.dtype).at[src_tok].add(contrib)
    return out


def moe_mlp_ep(params: dict, x: jax.Array, cfg, ep_axes: tuple[str, ...],
               tp_axis: str | None) -> jax.Array:
    """The shard_map body: x (T_loc, d) with experts sharded over
    ``ep_axes`` (weights arrive as local blocks (E_loc, d, f_loc)) and
    ffn dim over ``tp_axis``.

    all_to_all #1 ships each source shard's per-expert slots to the
    expert's owner; all_to_all #2 ships results back.
    """
    T, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    n_ep = 1
    for a in ep_axes:
        n_ep *= jax.lax.axis_size(a)
    E_loc = E // n_ep
    C = max(1, int(T * k / E * cfg.moe_capacity_factor))

    gates, idx = route(params["router"], x, cfg)
    flat_e = idx.reshape(T * k)
    flat_g = gates.reshape(T * k)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    perm, dst, keep = _dispatch_indices(flat_e, E, C)
    src_tok = flat_t[perm]
    src_gate = jnp.where(keep, flat_g[perm], 0.0)

    buf = jnp.zeros((E * C, d), x.dtype)
    vals = jnp.where(keep[:, None], x[src_tok], 0)
    buf = buf.at[dst].add(vals, mode="drop")
    send = buf.reshape(E, C, d)

    # EP all-to-all: (E, C, d) → (E_loc, n_ep·C, d), slots grouped by src
    recv = jax.lax.all_to_all(send, ep_axes, split_axis=0, concat_axis=1,
                              tiled=True)

    g = jnp.einsum("ecd,edf->ecf", recv, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", recv, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)      # partial down-proj over f_loc

    # return trip: (E_loc, n_ep·C, d) → (E, C, d)
    back = jax.lax.all_to_all(y, ep_axes, split_axis=1, concat_axis=0,
                              tiled=True)
    back = back.reshape(E * C, d)

    contrib = back[dst] * src_gate[:, None].astype(back.dtype)
    out = jnp.zeros((T, d), back.dtype).at[src_tok].add(contrib)
    return out


def moe_dense_reference(params: dict, x: jax.Array, cfg) -> jax.Array:
    """Oracle: every expert computed for every token, gate-weighted sum.
    Exact match to moe_mlp when capacity_factor admits all tokens."""
    gates, idx = route(params["router"], x, cfg)       # (T,k)
    g = jnp.einsum("td,edf->tef", x, params["w_gate"])
    u = jnp.einsum("td,edf->tef", x, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.einsum("tef,efd->ted", h, params["w_down"])   # (T,E,d)
    T, E = x.shape[0], cfg.num_experts
    dense_gate = jnp.zeros((T, E), jnp.float32)
    dense_gate = dense_gate.at[
        jnp.arange(T)[:, None], idx].add(gates)
    return jnp.einsum("te,ted->td", dense_gate.astype(y.dtype), y)
