"""Decoder-only transformer covering the dense / moe / vlm families.

Layers are stacked into *super-blocks* and iterated with ``lax.scan`` so the
lowered HLO is depth-independent (required to compile 40-48 layer targets for
512 host devices). A super-block spans ``period`` physical layers, where
``period = lcm(len(attn_pattern), moe interleave)`` — e.g. gemma2's
(local, global) alternation scans 23 blocks of 2, llama4's
(local,local,local,global+NoPE) × interleaved-MoE scans 12 blocks of 4.

EAGLE hidden-state taps (layers 2, L/2, L-1 per the paper) are collected in
the scan carry with predicated selects, so no (L, B, S, D) stack is ever
materialized.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.moe import moe_apply, moe_init
from repro.sharding.utils import shard_hint

Array = jax.Array


@dataclass
class ModelOutput:
    logits: Array
    taps: Optional[Array]          # (B, S, num_taps * D)
    cache: Any
    aux: dict


def tap_layers(n_layers: int, num_taps: int = 3):
    """EAGLE-3 tap layer indices (output-of-layer), paper Fig. 2: 2, L/2, L-1."""
    if num_taps == 1 or n_layers < 3:
        return (n_layers - 1,) * num_taps
    return (min(2, n_layers - 1), n_layers // 2, n_layers - 1)


def block_period(cfg: ModelConfig) -> int:
    p = len(cfg.attn_pattern)
    if cfg.moe.n_experts and cfg.moe.pattern == "interleaved":
        p = math.lcm(p, 2)
    return p


# ---------------------------------------------------------------------------
# attention layer
# ---------------------------------------------------------------------------

def attn_init(key: Array, d: int, n_heads: int, n_kv: int, hd: int,
              qkv_bias: bool, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], (d, n_heads * hd), dtype=dtype),
        "wk": L.dense_init(ks[1], (d, n_kv * hd), dtype=dtype),
        "wv": L.dense_init(ks[2], (d, n_kv * hd), dtype=dtype),
        "wo": L.dense_init(ks[3], (n_heads * hd, d), dtype=dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((n_kv * hd,), dtype)
        p["bv"] = jnp.zeros((n_kv * hd,), dtype)
    return p


def attn_apply(p: dict, x: Array, *, cfg: ModelConfig, kind: str,
               positions: Array, cache: Optional[dict],
               mode: str) -> tuple:
    """kind: global | local | full. mode: train | prefill | decode.

    Returns (out, new_cache)."""
    B, T, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, H, hd)
    k = k.reshape(B, T, KV, hd)
    v = v.reshape(B, T, KV, hd)
    q = shard_hint(q, ("pod", "data"), None, "model")
    k = shard_hint(k, ("pod", "data"), None, "model")

    use_rope = cfg.positional == "rope" and not (
        kind == "global" and cfg.nope_on_global)
    if use_rope:
        sin, cos = L.rope_sincos(positions, hd, cfg.rope_theta)
        q = L.apply_rope(q, sin, cos)
        k = L.apply_rope(k, sin, cos)

    window = cfg.window_size if kind == "local" else 0
    scale = cfg.q_scale()

    if mode == "decode":
        assert cache is not None
        pos0 = positions[:, 0]
        paged = L.is_paged(cache)
        # a paged cache is read from its pages through the block table:
        # the same view, one layer at a time
        if paged:
            with jax.named_scope("gather"):
                ck, cv, cpos = L.paged_view(cache, q.dtype)
        else:
            ck, cv = cache["k"].astype(q.dtype), cache["v"].astype(q.dtype)
            cpos = cache["positions"]
        # two-phase: attend [old cache] + [current block], merge by LSE,
        # THEN insert. Avoids copying the cache and — critically for ring
        # (sliding-window) caches — avoids evicting in-window entries the
        # current queries still need to read.
        old_kpos = jnp.where(cpos >= pos0[:, None], -1,
                             cpos)                 # mask stale history
        mask1 = L.cache_mask_fn(positions, old_kpos, window=window)
        o1, m1, l1 = L.blocked_attention(
            q, ck, cv, scale=scale, mask_fn=mask1,
            logit_cap=cfg.logit_softcap, return_stats=True)
        mask2 = L.cache_mask_fn(positions, positions, window=window)
        o2, m2, l2 = L.blocked_attention(
            q, k, v, scale=scale, mask_fn=mask2,
            logit_cap=cfg.logit_softcap, return_stats=True)
        out = L.merge_attention(o1, m1, l1, o2, m2, l2)
        if paged:
            with jax.named_scope("scatter"):
                cache = L.paged_update(cache, k, v, pos0)
        else:
            cache = L.cache_update(cache, k, v, pos0)
    else:
        if cache is not None:  # prefill: also populate the cache
            ins = min(T, cache["k"].shape[1])
            cache = L.cache_update(cache, k[:, -ins:], v[:, -ins:],
                                   positions[:, T - ins])
        if kind == "full":
            mask = None
        elif window:
            mask = L.local_mask_fn(positions, window)
        else:
            mask = L.causal_mask_fn(positions)
        out = L.blocked_attention(q, k, v, scale=scale, mask_fn=mask,
                                  logit_cap=cfg.logit_softcap)
    out = out.reshape(B, T, H * hd) @ p["wo"]
    return out, cache


# ---------------------------------------------------------------------------
# block = [norm, attn, (post-norm), norm, mlp/moe, (post-norm)]
# ---------------------------------------------------------------------------

def _slot_init(cfg: ModelConfig, key: Array, layer_idx: int, dtype) -> dict:
    ka, km = jax.random.split(key)
    p = {
        "ln1": jnp.ones((cfg.d_model,), jnp.float32),
        "ln2": jnp.ones((cfg.d_model,), jnp.float32),
        "attn": attn_init(ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim, cfg.qkv_bias, dtype),
    }
    if cfg.post_norms:
        p["pn1"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["pn2"] = jnp.ones((cfg.d_model,), jnp.float32)
    if cfg.is_moe_layer(layer_idx):
        p["moe"] = moe_init(km, cfg.d_model, cfg.d_ff, cfg.moe.n_experts,
                            cfg.moe.n_shared_experts, cfg.mlp_variant, dtype)
    else:
        p["mlp"] = L.mlp_init(km, cfg.d_model, cfg.d_ff, cfg.mlp_variant, dtype)
    return p


def _slot_apply(cfg: ModelConfig, p: dict, x: Array, *, layer_idx: int,
                positions: Array, cache: Optional[dict], mode: str):
    kind = cfg.attn_kind(layer_idx)
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    a, cache = attn_apply(p["attn"], h, cfg=cfg, kind=kind,
                          positions=positions, cache=cache, mode=mode)
    if cfg.post_norms:
        a = L.rms_norm(a, p["pn1"], cfg.norm_eps)
    x = x + a
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    aux = None
    if "moe" in p:
        f, aux = moe_apply(p["moe"], h, n_experts=cfg.moe.n_experts,
                           top_k=cfg.moe.top_k,
                           capacity_factor=cfg.moe.capacity_factor,
                           variant=cfg.mlp_variant,
                           n_shared=cfg.moe.n_shared_experts)
    else:
        f = L.mlp_apply(p["mlp"], h, cfg.mlp_variant)
    if cfg.post_norms:
        f = L.rms_norm(f, p["pn2"], cfg.norm_eps)
    x = x + f
    x = shard_hint(x, ("pod", "data"), None, None)
    return x, cache, aux


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: Array) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    period = block_period(cfg)
    n_sb, tail = divmod(cfg.n_layers, period)
    keys = jax.random.split(key, 4)

    def block_init(bkey, base_idx):
        sk = jax.random.split(bkey, period)
        return {f"slot{i}": _slot_init(cfg, sk[i], base_idx + i, dtype)
                for i in range(period)}

    bkeys = jax.random.split(keys[0], n_sb)
    blocks = jax.vmap(lambda k: block_init(k, 0))(bkeys)
    # NOTE: is_moe_layer / attn_kind depend on layer_idx % period only, so
    # base_idx=0 gives every block the right per-slot structure.

    params = {
        "embed": L.embed_init(keys[1], cfg.vocab_size, cfg.d_model, dtype),
        "blocks": blocks,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if tail:
        tkeys = jax.random.split(keys[2], tail)
        params["tail"] = {f"slot{i}": _slot_init(cfg, tkeys[i],
                                                 n_sb * period + i, dtype)
                          for i in range(tail)}
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[3], (cfg.d_model, cfg.vocab_size),
                                         dtype=dtype)
    if cfg.family == "vlm":
        kv1, kv2 = jax.random.split(keys[3] if cfg.tie_embeddings else keys[2])
        params["vis_proj"] = {
            "w1": L.dense_init(kv1, (cfg.vision_dim, cfg.d_model), dtype=dtype),
            "w2": L.dense_init(kv2, (cfg.d_model, cfg.d_model), dtype=dtype),
        }
    return params


def make_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    """Per-slot stacked KV caches; local-attention slots get ring buffers of
    window length (this is what makes long_500k decode memory bounded)."""
    period = block_period(cfg)
    n_sb, tail = divmod(cfg.n_layers, period)

    def slot_cache(kind, stack: Optional[int]):
        ring = kind == "local" and cfg.window_size < max_len
        ln = min(cfg.window_size, max_len) if ring else max_len
        c = L.make_kv_cache(batch, ln, cfg.n_kv_heads, cfg.head_dim,
                            dtype=dtype, ring=ring)
        if stack is not None:
            c = jax.tree.map(lambda a: jnp.broadcast_to(
                a, (stack,) + a.shape).copy(), c)
        return c

    cache = {"blocks": {f"slot{i}": slot_cache(cfg.attn_kind(i), n_sb)
                        for i in range(period)}}
    if tail:
        cache["tail"] = {f"slot{i}": slot_cache(
            cfg.attn_kind(n_sb * period + i), None) for i in range(tail)}
    return cache


def forward(cfg: ModelConfig, params: dict, tokens: Array, *,
            positions: Optional[Array] = None,
            cache: Optional[dict] = None,
            mode: str = "train",
            vision_embeds: Optional[Array] = None,
            collect_taps: bool = True,
            head_last_only: bool = False,
            head_positions: Optional[Array] = None) -> ModelOutput:
    """tokens (B, S). For vlm train/prefill, vision_embeds (B, Tv, vision_dim)
    are projected and prepended (early fusion); logits cover the full fused
    sequence. ``head_positions`` (B,) restricts the LM head to one gathered
    sequence index per row (bucketed prefill: the true last prompt position
    inside a padded bucket), like ``head_last_only`` does for index -1."""
    B = tokens.shape[0]
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.family == "vlm" and vision_embeds is not None:
        vp = params["vis_proj"]
        vis = jax.nn.gelu(vision_embeds.astype(x.dtype) @ vp["w1"]) @ vp["w2"]
        x = jnp.concatenate([vis, x], axis=1)
    S = x.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if cfg.positional == "sinusoidal":
        x = x + L.sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
    x = shard_hint(x, ("pod", "data"), None, None)

    period = block_period(cfg)
    n_sb = cfg.n_layers // period
    taps_idx = tap_layers(cfg.n_layers)
    taps0 = jnp.zeros((len(taps_idx), B, S, cfg.d_model), x.dtype)

    def run_block(x, taps, bparams, bcache, base_idx):
        new_cache = {} if bcache is not None else None
        aux_lb = jnp.zeros((), jnp.float32)
        aux_z = jnp.zeros((), jnp.float32)
        for i in range(period):
            sl = f"slot{i}"
            x, sc, aux = _slot_apply(
                cfg, bparams[sl], x, layer_idx=i, positions=positions,
                cache=None if bcache is None else bcache[sl], mode=mode)
            if new_cache is not None:
                new_cache[sl] = sc
            if aux is not None:
                aux_lb += aux["lb_loss"]
                aux_z += aux["z_loss"]
            if collect_taps:
                li = base_idx + i
                sel = jnp.stack([jnp.asarray(li == t) for t in taps_idx])
                taps = jnp.where(sel[:, None, None, None], x[None], taps)
        return x, taps, new_cache, aux_lb, aux_z

    def scan_body(carry, xs):
        x, taps, lb, z, base = carry
        bparams, bcache = xs
        x, taps, ncache, alb, az = run_block(x, taps, bparams, bcache, base)
        return (x, taps, lb + alb, z + az, base + period), ncache

    bcaches = cache["blocks"] if cache is not None else None
    if bcaches is None:
        dummy = jnp.zeros((n_sb,), jnp.int32)
        (x, taps, lb, z, base), _ = jax.lax.scan(
            lambda c, xs_: (scan_body(c, (xs_[0], None))[0], None),
            (x, taps0, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
             jnp.zeros((), jnp.int32)),
            (params["blocks"], dummy))
        new_cache = None
    else:
        # a paged slot's stacked pools ride the carry, so each layer writes
        # its new rows into them in place (as scan ys they would be a whole
        # new pool per layer); its ring flag and block table stay xs
        pools = {sl: {n: c[n] for n in L.POOL_LEAVES}
                 for sl, c in bcaches.items() if L.is_paged(c) and n_sb}

        def paged_body(carry, xs):
            inner, pools = carry
            bparams, bcache = xs
            layer = inner[-1] // period              # this block's row
            bcache = {sl: {**c, **pools[sl], "layer": layer}
                      if sl in pools else c for sl, c in bcache.items()}
            inner, ncache = scan_body(inner, (bparams, bcache))
            pools = {sl: {n: ncache[sl][n] for n in L.POOL_LEAVES}
                     for sl in pools}
            ncache = {sl: {n: a for n, a in c.items()
                           if n not in L.POOL_LEAVES + ("layer",)}
                      if sl in pools else c for sl, c in ncache.items()}
            return (inner, pools), ncache

        inner0 = (x, taps0, jnp.zeros((), jnp.float32),
                  jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
        xs_cache = {sl: {n: a for n, a in c.items() if n not in L.POOL_LEAVES}
                    if sl in pools else c for sl, c in bcaches.items()}
        ((x, taps, lb, z, base), pools), new_bcache = jax.lax.scan(
            paged_body, (inner0, pools), (params["blocks"], xs_cache))
        new_cache = {"blocks": {sl: {**c, **pools[sl]} if sl in pools else c
                                for sl, c in new_bcache.items()}}

    # tail layers (when n_layers % period != 0)
    if "tail" in params:
        tcache = cache.get("tail") if cache is not None else None
        ntail = {}
        for i in range(len(params["tail"])):
            sl = f"slot{i}"
            li = n_sb * period + i
            x, sc, aux = _slot_apply(
                cfg, params["tail"][sl], x, layer_idx=li, positions=positions,
                cache=None if tcache is None else tcache[sl], mode=mode)
            ntail[sl] = sc
            if aux is not None:
                lb, z = lb + aux["lb_loss"], z + aux["z_loss"]
            if collect_taps:
                sel = jnp.stack([jnp.asarray(li == t) for t in taps_idx])
                taps = jnp.where(sel[:, None, None, None], x[None], taps)
        if new_cache is not None:
            new_cache["tail"] = ntail

    if head_positions is not None:
        x = jnp.take_along_axis(x, head_positions[:, None, None], axis=1)
    elif head_last_only:
        # prefill only consumes the last position's logits; computing the
        # full (B, S, vocab) tensor wastes memory+collectives (§Perf iter 2)
        x = x[:, -1:]
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head", None)
    logits = x @ head if head is not None else x @ params["embed"].T.astype(x.dtype)
    logits = L.softcap(logits.astype(jnp.float32), cfg.final_softcap)
    logits = shard_hint(logits, ("pod", "data"), None, "model")

    taps_out = None
    if collect_taps:
        taps_out = jnp.moveaxis(taps, 0, -2).reshape(B, S, -1)
    return ModelOutput(logits=logits, taps=taps_out, cache=new_cache,
                       aux={"lb_loss": lb, "z_loss": z})
