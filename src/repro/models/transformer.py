"""Decoder-only transformer trunk covering the dense / MoE / SSM /
hybrid / VLM families through the layer-kind pattern mechanism.

Layers are grouped into repeating *periods* (cfg.pattern); parameters
for each pattern position are stacked across periods and the stack is
consumed by one ``jax.lax.scan`` — HLO size stays O(|pattern|) no
matter how deep the model (94-layer qwen3-235b compiles as one period
body).  The non-divisible tail (recurrentgemma's 26 = 8·3 + 2) runs as
explicit layers after the scan.

Three entry points, matching the serving/training split:
  ``forward_train``  — full-sequence logits (no cache)
  ``prefill``        — full-sequence logits + populated caches
  ``decode_step``    — one token in, one logits column out, cache updated

Cache pytree layout (stacked like params):
  attention kinds  → {"k","v"}: (n_periods, B, S_kind, H_kv, dh)
  rglru            → {"h": (n,B,dr), "conv": (n,B,W-1,dr)}
  mlstm            → {"C","n","m"}; slstm → {"c","n","h","m"}
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models.config import ArchConfig
from repro.models.layers import (
    dense_init,
    dtype_of,
    embed,
    init_embedding,
    init_mlp,
    init_rmsnorm,
    mlp,
    rmsnorm,
    softcap,
    unembed,
)
from repro.models.runtime import LOCAL, Runtime

ATTN_KINDS = ("global", "local")


# ============================ init ==============================================
def init_layer(key, cfg: ArchConfig, kind: str, dtype) -> dict:
    if kind in ATTN_KINDS:
        k1, k2 = jax.random.split(key)
        p = {
            "ln1": init_rmsnorm(cfg.d_model),
            "attn": attn.init_attention(k1, cfg, dtype),
            "ln2": init_rmsnorm(cfg.d_model),
        }
        if cfg.is_moe:
            p["moe"] = moe_lib.init_moe(k2, cfg, dtype)
        else:
            p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind,
                                dtype)
        if cfg.use_post_norm:
            p["post_ln1"] = init_rmsnorm(cfg.d_model)
            p["post_ln2"] = init_rmsnorm(cfg.d_model)
        return p
    if kind == "rglru":
        k1, k2 = jax.random.split(key)
        return {
            "rec": rglru_lib.init_rglru_block(k1, cfg, dtype),
            "ln2": init_rmsnorm(cfg.d_model),
            "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind, dtype),
        }
    if kind == "mlstm":
        return {"cell": ssm_lib.init_mlstm_block(key, cfg, dtype)}
    if kind == "slstm":
        return {"cell": ssm_lib.init_slstm_block(key, cfg, dtype)}
    raise ValueError(f"unknown layer kind {kind!r}")


def init_params(key, cfg: ArchConfig) -> dict:
    dtype = dtype_of(cfg.dtype)
    keys = jax.random.split(key, 4 + len(cfg.tail_kinds))
    params: dict[str, Any] = {
        "embed": init_embedding(keys[0], cfg.padded_vocab, cfg.d_model,
                                dtype),
        "final_norm": init_rmsnorm(cfg.d_model),
    }
    if cfg.num_vision_tokens:
        params["vision_proj"] = dense_init(
            keys[1], (cfg.d_model, cfg.d_model), dtype)
    # stacked periods: vmap init over per-period keys
    period = {}
    pkeys = jax.random.split(keys[2], len(cfg.pattern))
    for i, kind in enumerate(cfg.pattern):
        lkeys = jax.random.split(pkeys[i], cfg.n_periods)
        period[f"k{i}"] = jax.vmap(
            lambda k, kind=kind: init_layer(k, cfg, kind, dtype))(lkeys)
    params["periods"] = period
    for j, kind in enumerate(cfg.tail_kinds):
        params[f"tail{j}"] = init_layer(keys[3 + j], cfg, kind, dtype)
    return params


# ============================ caches ============================================
def init_layer_cache(cfg: ArchConfig, kind: str, batch: int, max_seq: int,
                     rt: Runtime) -> dict:
    if kind in ATTN_KINDS:
        return attn.init_kv_cache(batch, max_seq, cfg, rt.cache_dtype(),
                                  kind)
    if kind == "rglru":
        return rglru_lib.rglru_state(batch, cfg)
    if kind == "mlstm":
        return ssm_lib.mlstm_state(batch, cfg)
    if kind == "slstm":
        return ssm_lib.slstm_state(batch, cfg)
    raise ValueError(kind)


def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               rt: Runtime = LOCAL) -> dict:
    cache: dict[str, Any] = {"periods": {}}
    for i, kind in enumerate(cfg.pattern):
        one = init_layer_cache(cfg, kind, batch, max_seq, rt)
        cache["periods"][f"k{i}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(
                x, (cfg.n_periods,) + x.shape).copy(), one)
    for j, kind in enumerate(cfg.tail_kinds):
        cache[f"tail{j}"] = init_layer_cache(cfg, kind, batch, max_seq, rt)
    return cache


# ============================ layer application ===================================
def _apply_mlp(params: dict, x: jax.Array, cfg: ArchConfig, rt: Runtime
               ) -> jax.Array:
    """Dense MLP or MoE, with the MoE distribution strategy applied."""
    if not cfg.is_moe:
        return mlp(params["mlp"], x, cfg.mlp_kind)
    B, S, d = x.shape
    tokens = x.reshape(B * S, d)
    if rt.moe == "ep" and rt.mesh is not None:
        P = jax.sharding.PartitionSpec
        ep = rt.ep_axes if len(rt.ep_axes) > 1 else (
            rt.ep_axes[0] if rt.ep_axes else None)
        tp = rt.tp_axis
        specs = {
            "router": P(None, None),
            "w_gate": P(ep, None, tp),
            "w_up": P(ep, None, tp),
            "w_down": P(ep, tp, None),
        }
        fn = functools.partial(moe_lib.moe_mlp_ep, cfg=cfg,
                               ep_axes=rt.ep_axes, tp_axis=rt.tp_axis)
        out = jax.shard_map(
            fn, mesh=rt.mesh,
            in_specs=(specs, P(rt.dp, None)),
            out_specs=P(rt.dp, None),
        )(params["moe"], tokens)
    else:
        out = moe_lib.moe_mlp(params["moe"], tokens, cfg)
    return out.reshape(B, S, d)


def apply_layer(params: dict, x: jax.Array, cfg: ArchConfig, kind: str,
                mode: str, positions: jax.Array,
                cache: Optional[dict], cur_index, rt: Runtime
                ) -> tuple[jax.Array, Optional[dict]]:
    """One residual layer of the given kind.  Returns (x, new_cache)."""
    if kind in ATTN_KINDS:
        y = rmsnorm(params["ln1"], x)
        if mode == "train":
            y = attn.attention_block(params["attn"], y, cfg, kind,
                                     positions)
            new_kv = None
        elif mode == "prefill":
            y, new_kv = attn.prefill_attention(params["attn"], y, cfg,
                                               kind, positions, cache,
                                               blocked=rt.blocked_attn,
                                               block_k=rt.attn_block_k)
        else:
            y, new_kv = attn.decode_attention(
                params["attn"], y, cfg, kind, cache, cur_index,
                onehot_update=rt.onehot_cache_update,
                grouped_gqa=rt.grouped_gqa_decode)
        if cfg.use_post_norm:
            y = rmsnorm(params["post_ln1"], y)
        x = x + y
        y = rmsnorm(params["ln2"], x)
        y = _apply_mlp(params, y, cfg, rt)
        if cfg.use_post_norm:
            y = rmsnorm(params["post_ln2"], y)
        return x + y, new_kv

    if kind == "rglru":
        if mode == "decode":
            x, new_state = rglru_lib.rglru_decode_step(params["rec"], x,
                                                       cache)
        else:
            state = cache if cache is not None else \
                rglru_lib.rglru_state(x.shape[0], cfg)
            x, new_state = rglru_lib.rglru_block(params["rec"], x, state)
        y = rmsnorm(params["ln2"], x)
        x = x + _apply_mlp(params, y, cfg, rt)
        return x, (new_state if mode != "train" else None)

    if kind == "mlstm":
        state = cache if cache is not None else \
            ssm_lib.mlstm_state(x.shape[0], cfg)
        x, new_state = ssm_lib.mlstm_block(params["cell"], x, state)
        return x, (new_state if mode != "train" else None)

    if kind == "slstm":
        state = cache if cache is not None else \
            ssm_lib.slstm_state(x.shape[0], cfg)
        x, new_state = ssm_lib.slstm_block(params["cell"], x, state)
        return x, (new_state if mode != "train" else None)

    raise ValueError(kind)


# ============================ trunk ==============================================
def embed_inputs(params: dict, tokens: jax.Array, cfg: ArchConfig,
                 extra_embed: Optional[jax.Array] = None) -> jax.Array:
    """Token embeddings, optionally prefixed with projected modality
    embeddings (VLM patch tokens / audio frames)."""
    x = embed(params["embed"], tokens, scale_by_sqrt_dim=cfg.embed_scale)
    if extra_embed is not None:
        v = jnp.einsum("bnd,de->bne", extra_embed.astype(x.dtype),
                       params["vision_proj"])
        x = jnp.concatenate([v, x], axis=1)
    return x


def _run_layers(params: dict, x: jax.Array, cfg: ArchConfig, mode: str,
                positions: jax.Array, cache: Optional[dict],
                cur_index, rt: Runtime
                ) -> tuple[jax.Array, Optional[dict]]:
    x = rt.constrain(x, rt.dp, None, None)

    def body(h, xs):
        pparams, pcache = xs
        new_caches = {}
        for i, kind in enumerate(cfg.pattern):
            c = pcache[f"k{i}"] if pcache is not None else None
            h, nc = apply_layer(pparams[f"k{i}"], h, cfg, kind, mode,
                                positions, c, cur_index, rt)
            h = rt.constrain(h, rt.dp, None, None)
            if nc is not None:
                new_caches[f"k{i}"] = nc
        return h, (new_caches if new_caches else None)

    if mode == "train" and rt.remat == "full":
        # activation checkpointing per layer period: backward recomputes
        # the period body — O(1) stored activations per layer instead of
        # O(S²) attention internals (required at train_4k scale)
        body = jax.checkpoint(body)

    pcaches = cache["periods"] if cache is not None else None
    x, new_period_caches = jax.lax.scan(
        body, x, (params["periods"], pcaches), unroll=rt.scan_unroll)

    new_cache: Optional[dict] = None
    if mode != "train":
        new_cache = {"periods": new_period_caches}
    for j, kind in enumerate(cfg.tail_kinds):
        c = cache[f"tail{j}"] if cache is not None else None
        x, nc = apply_layer(params[f"tail{j}"], x, cfg, kind, mode,
                            positions, c, cur_index, rt)
        if new_cache is not None:
            new_cache[f"tail{j}"] = nc
    return x, new_cache


def _logits(params: dict, x: jax.Array, cfg: ArchConfig, rt: Runtime
            ) -> jax.Array:
    x = rmsnorm(params["final_norm"], x)
    logits = unembed(params["embed"], x, cfg.vocab_size,
                     cap=cfg.final_logit_softcap)
    return rt.constrain(logits, rt.dp, None, rt.tp_axis)


def forward_train(params: dict, tokens: jax.Array, cfg: ArchConfig,
                  rt: Runtime = LOCAL,
                  extra_embed: Optional[jax.Array] = None) -> jax.Array:
    """(B,S) tokens → (B,S',V_padded) logits (S' includes modality prefix)."""
    x = embed_inputs(params, tokens, cfg, extra_embed)
    positions = jnp.broadcast_to(
        jnp.arange(x.shape[1], dtype=jnp.int32)[None, :],
        (x.shape[0], x.shape[1]))
    x, _ = _run_layers(params, x, cfg, "train", positions, None, None, rt)
    return _logits(params, x, cfg, rt)


def prefill(params: dict, tokens: jax.Array, cfg: ArchConfig,
            cache: dict, rt: Runtime = LOCAL,
            extra_embed: Optional[jax.Array] = None
            ) -> tuple[jax.Array, dict]:
    """Populate caches over the prompt; returns last-position logits."""
    x = embed_inputs(params, tokens, cfg, extra_embed)
    positions = jnp.broadcast_to(
        jnp.arange(x.shape[1], dtype=jnp.int32)[None, :],
        (x.shape[0], x.shape[1]))
    x, new_cache = _run_layers(params, x, cfg, "prefill", positions,
                               cache, None, rt)
    logits = _logits(params, x[:, -1:, :], cfg, rt)
    return logits, new_cache


def decode_step(params: dict, token: jax.Array, cfg: ArchConfig,
                cache: dict, cur_index, rt: Runtime = LOCAL
                ) -> tuple[jax.Array, dict]:
    """token (B,1) at position ``cur_index`` (scalar or per-sequence
    (B,) vector) → (B,1,V) logits + updated caches."""
    x = embed_inputs(params, token, cfg)
    cur = jnp.broadcast_to(jnp.asarray(cur_index, jnp.int32),
                           (x.shape[0],))
    positions = cur[:, None]
    x, new_cache = _run_layers(params, x, cfg, "decode", positions,
                               cache, cur, rt)
    return _logits(params, x, cfg, rt), new_cache
