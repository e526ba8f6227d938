"""Shared neural-net primitives for the target-model zoo and the drafter.

Everything is pure JAX on explicit parameter pytrees (no flax). Conventions:

- activations: ``(B, S, D)``; attention heads ``(B, S, H, head_dim)``.
- parameters are stored in float32 ("master") unless a caller casts them;
  forward code computes in ``compute_dtype`` with float32 softmax/accums.
- attention is *blocked*: an online-softmax ``lax.scan`` over KV blocks, so
  the lowered HLO never materializes an (Sq, Skv) score matrix. This is the
  CPU/dry-run twin of the Pallas ``flash_attention`` kernel (kernels/).
- masks are pluggable predicates over absolute positions, which is how the
  P-EAGLE closed-form MTP mask (core/masks.py) plugs into the same machinery.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

Array = jax.Array
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key: Array, shape, scale: Optional[float] = None,
               dtype=jnp.float32) -> Array:
    """Truncated-normal fan-in init (matches common LLM inits)."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return std * jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32).astype(dtype)


def embed_init(key: Array, vocab: int, d: int, dtype=jnp.float32) -> Array:
    return 0.02 * jax.random.truncated_normal(key, -3.0, 3.0, (vocab, d), jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# norms / positions / activations
# ---------------------------------------------------------------------------

def rms_norm(x: Array, w: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (w.astype(jnp.float32))
    return out.astype(dt)


def rope_sincos(positions: Array, head_dim: int, theta: float):
    """positions (..., T) int -> sin/cos (..., T, head_dim//2) float32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: Array, sin: Array, cos: Array) -> Array:
    """x (B, T, H, hd); sin/cos (B, T, hd/2) or (T, hd/2)."""
    dt = x.dtype
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    if sin.ndim == 2:  # (T, half)
        s, c = sin[None, :, None, :], cos[None, :, None, :]
    else:              # (B, T, half)
        s, c = sin[:, :, None, :], cos[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(dt)


def sinusoidal_positions(positions: Array, d: int) -> Array:
    """Whisper-style absolute sinusoidal embeddings, (..., T) -> (..., T, d)."""
    half = d // 2
    scale = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(10_000.0) / max(half - 1, 1)))
    ang = positions.astype(jnp.float32)[..., None] * scale
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def softcap(x: Array, cap: float) -> Array:
    return cap * jnp.tanh(x / cap) if cap > 0.0 else x


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------

def mlp_init(key: Array, d: int, f: int, variant: str, dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    if variant in ("swiglu", "geglu"):
        return {"w_gate": dense_init(k1, (d, f), dtype=dtype),
                "w_up": dense_init(k2, (d, f), dtype=dtype),
                "w_down": dense_init(k3, (f, d), dtype=dtype)}
    return {"w_up": dense_init(k1, (d, f), dtype=dtype),
            "w_down": dense_init(k2, (f, d), dtype=dtype)}


def mlp_apply(p: dict, x: Array, variant: str) -> Array:
    if variant == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif variant == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"], approximate=True) * (x @ p["w_up"])
    elif variant == "relu2":
        h = jnp.square(jax.nn.relu(x @ p["w_up"]))
    elif variant == "gelu":
        h = jax.nn.gelu(x @ p["w_up"], approximate=True)
    else:
        raise ValueError(f"unknown mlp variant {variant!r}")
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# blocked attention (online softmax over KV blocks)
# ---------------------------------------------------------------------------

MaskFn = Callable[[Array, Array], Array]  # (q_idx (Sq,), k_idx (Bk,)) -> bool


def causal_mask_fn(q_positions: Array) -> MaskFn:
    """q_positions: (B, Sq) absolute positions; keys are indexed 0..Skv-1 and
    key slot j holds absolute position j (contiguous, non-ring layout)."""
    def fn(q_idx, k_idx):
        qp = jnp.take(q_positions, q_idx, axis=-1)        # (B, Sq)
        ok = qp[:, :, None] >= k_idx[None, None, :]
        return ok[:, None, None]                          # (B,1,1,Sq,Bk)
    return fn


def local_mask_fn(q_positions: Array, window: int) -> MaskFn:
    def fn(q_idx, k_idx):
        qp = jnp.take(q_positions, q_idx, axis=-1)
        d = qp[:, :, None] - k_idx[None, None, :]
        ok = (d >= 0) & (d < window)
        return ok[:, None, None]
    return fn


def cache_mask_fn(q_positions: Array, k_positions: Array,
                  window: int = 0) -> MaskFn:
    """Decode against a (possibly ring) cache with stored absolute positions.

    q_positions (B, Sq); k_positions (B, W) with -1 for empty slots.
    """
    def fn(q_idx, k_idx):
        qp = jnp.take(q_positions, q_idx, axis=-1)        # (B, Sq)
        kp = jnp.take(k_positions, k_idx, axis=-1)        # (B, Bk)
        ok = (kp[:, None, :] <= qp[:, :, None]) & (kp[:, None, :] >= 0)
        if window > 0:
            ok &= (qp[:, :, None] - kp[:, None, :]) < window
        return ok[:, None, None]                          # (B,1,1,Sq,Bk)
    return fn


def _pick_block(skv: int, want: int = 512) -> int:
    b = min(want, skv)
    while skv % b:
        b -= 1
    return max(b, 1)


def blocked_attention(q: Array, k: Array, v: Array, *,
                      scale: float,
                      mask_fn: Optional[MaskFn] = None,
                      logit_cap: float = 0.0,
                      block_k: int = 512,
                      return_stats: bool = False):
    """Flash-style attention in pure jnp.

    q (B, Sq, H, hd); k/v (B, Skv, KV, hd) with H % KV == 0 (GQA).
    mask_fn maps absolute (q_idx, k_idx) index vectors to a boolean array
    broadcastable to (B, KV, G, Sq, Bk). Accumulation is float32.

    With return_stats=True also returns the online-softmax (m, l) so two
    attention passes over disjoint key sets can be merged exactly
    (merge_attention) — used by the decode path to attend [old cache] and
    [current block] without copying the cache.
    """
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    bk = _pick_block(Skv, block_k)
    n_blocks = Skv // bk

    qr = q.reshape(B, Sq, KV, G, hd)
    kb = k.reshape(B, n_blocks, bk, KV, hd)
    vb = v.reshape(B, n_blocks, bk, KV, hd)

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        jblk, kj, vj = inp
        s = jnp.einsum("bqkgd,bjkd->bkgqj", qr, kj,
                       preferred_element_type=jnp.float32) * scale
        s = softcap(s, logit_cap)
        k_idx = jblk * bk + jnp.arange(bk)
        ok = None
        if mask_fn is not None:
            ok = mask_fn(jnp.arange(Sq), k_idx)
            s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        if ok is not None:   # fully-masked rows: exp(-inf - -inf) = 1
            p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        # cast p to the value dtype: a mixed f32×bf16 einsum upcasts its
        # bf16 operand, and XLA hoists that convert out of the KV loop —
        # materializing a full f32 copy of the cache (§Perf iteration 1).
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqj,bjkd->bkgqd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.arange(n_blocks), kb.swapaxes(0, 1), vb.swapaxes(0, 1)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.where((l > 0)[..., None], out, 0.0)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)
    if return_stats:
        return out, m, l
    return out


def merge_attention(o1: Array, m1: Array, l1: Array,
                    o2: Array, m2: Array, l2: Array) -> Array:
    """Exact merge of two online-softmax passes over disjoint key sets.

    o* (B, Sq, H, hd) normalized outputs; m*/l* (B, KV, G, Sq)."""
    B, Sq, H, hd = o1.shape
    KV = m1.shape[1]
    G = m1.shape[2]
    m = jnp.maximum(m1, m2)
    w1 = l1 * jnp.exp(m1 - m)
    w2 = l2 * jnp.exp(m2 - m)
    l = w1 + w2
    w1 = (w1 / jnp.maximum(l, 1e-30))
    w2 = (w2 / jnp.maximum(l, 1e-30))
    # reshape weights (B,KV,G,Sq) -> (B,Sq,H,1)
    def rs(w):
        return w.transpose(0, 3, 1, 2).reshape(B, Sq, H)[..., None]
    out = o1.astype(jnp.float32) * rs(w1) + o2.astype(jnp.float32) * rs(w2)
    out = jnp.where(rs(l > 0) > 0, out, 0.0)
    return out.astype(o1.dtype)


def full_attention(q: Array, k: Array, v: Array, *, scale: float,
                   mask: Optional[Array] = None, logit_cap: float = 0.0) -> Array:
    """Unblocked reference path (short sequences, e.g. whisper encoder)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qr = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgd,bjkd->bkgqj", qr, k,
                   preferred_element_type=jnp.float32) * scale
    s = softcap(s, logit_cap)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqj,bjkd->bkgqd", p, v,
                     preferred_element_type=jnp.float32)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------

def make_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16, ring: bool = False) -> dict:
    """A single layer's KV cache. ``positions`` stores absolute positions of
    each slot (-1 = empty) so ring (sliding-window) caches mask correctly."""
    return {
        "k": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "positions": jnp.full((batch, max_len), -1, jnp.int32),
        "ring": jnp.array(ring),
    }


def cache_update(cache: dict, k_new: Array, v_new: Array,
                 pos: Array) -> dict:
    """Insert T new tokens at per-row absolute positions ``pos`` (B,).

    For ring caches the slot is ``position % W``. Any existing entry with
    position >= pos is *stale history being rewritten* (speculative decoding
    rolls back rejected drafts) and is invalidated first. Returns the updated
    cache.
    """
    B, T = k_new.shape[0], k_new.shape[1]
    W = cache["k"].shape[1]
    stale = cache["positions"] >= pos[:, None]
    cache = dict(cache)
    cache["positions"] = jnp.where(stale, -1, cache["positions"])
    abs_pos = pos[:, None] + jnp.arange(T)[None, :]          # (B, T)
    slot = jnp.where(cache["ring"], abs_pos % W, abs_pos)

    def upd_row(buf_k, buf_v, buf_p, kr, vr, sl, ap):
        bk = buf_k.at[sl].set(kr.astype(buf_k.dtype))
        bv = buf_v.at[sl].set(vr.astype(buf_v.dtype))
        bp = buf_p.at[sl].set(ap)
        return bk, bv, bp

    k2, v2, p2 = jax.vmap(upd_row)(cache["k"], cache["v"], cache["positions"],
                                   k_new, v_new, slot, abs_pos)
    return {"k": k2, "v": v2, "positions": p2, "ring": cache["ring"]}


# ---------------------------------------------------------------------------
# paged KV caches (serving/cache_ops.py): pools read and written in place
# ---------------------------------------------------------------------------

POOL_LEAVES = ("k", "v", "positions")


def is_paged(cache: Optional[dict]) -> bool:
    """A paged KV cache: the pools of ``make_kv_cache``'s leaves, k/v
    ``(NP, page, KV, hd)`` and positions ``(NP, page)``, with the slots'
    ``block_table`` (B, nb) beside them. A ``layer`` index, where present,
    says the pools are stacked over layers and this layer is that row."""
    return isinstance(cache, dict) and "block_table" in cache


def _pool_index(cache: dict, *idx):
    layer = cache.get("layer")
    return idx if layer is None else (layer,) + idx


def paged_view(cache: dict, dtype) -> tuple:
    """One layer's (k, v, positions) as the contiguous per-slot view,
    k/v ``(B, nb*page, KV, hd)`` in ``dtype`` and positions ``(B, nb*page)``,
    read from its pages through the block table (the layer's share of
    ``cache_ops.gather_pages``). Unmapped entries (-1) read page 0 with
    their positions forced to -1, so they are empty to every mask."""
    table = cache["block_table"]
    B, nb = table.shape
    page = cache["positions"].shape[-1]
    idx = _pool_index(cache, jnp.clip(table, 0, None))

    def read(pool):
        # a page's (page, KV, hd) as (page*KV, hd) rows, the same bytes: a
        # TPU v5e gathers these twice as fast as pages whose two minor dims
        # (KV, hd) tile as 2-row tiles
        rows = pool.reshape(pool.shape[:-3] + (-1, pool.shape[-1]))
        v = rows[idx]                                # (B, nb, page*KV, hd)
        return v.reshape((B, nb * page) + pool.shape[-2:]).astype(dtype)

    pos = cache["positions"][idx].reshape(B, nb * page)
    pos = jnp.where(jnp.repeat(table < 0, page, axis=1), -1, pos)
    return read(cache["k"]), read(cache["v"]), pos


def paged_rows(cache: dict, pos: Array, T: int):
    """Pool index of rows ``pos[b] .. pos[b]+T-1`` of each slot: (page,
    offset) arrays (B, T). A row past the slot's mapped pages (table -1,
    or beyond max_len) gets the out-of-range page ``NP``, so a write with
    ``mode="drop"`` skips it, as the view's scatter drops it."""
    table = cache["block_table"]
    nb = table.shape[1]
    NP, page = cache["positions"].shape[-2:]
    rows = pos[:, None] + jnp.arange(T, dtype=pos.dtype)[None, :]
    blk = rows // page
    pg = jnp.take_along_axis(table, jnp.clip(blk, 0, nb - 1), axis=1)
    pg = jnp.where((blk < nb) & (pg >= 0), pg, NP)
    return _pool_index(cache, pg, rows % page), rows


def paged_update(cache: dict, k_new: Array, v_new: Array,
                 pos: Array) -> dict:
    """``cache_update`` on a paged cache, in place: the T new rows of each
    slot (absolute positions ``pos[b] .. pos[b]+T-1``) go into their pages
    and nothing else is written. Rows at and past ``pos`` hold no valid
    entry beyond the ones written here (commit invalidates each rejected
    row, ``cache_ops.commit``), so the stale-history pass of the
    contiguous path has nothing to clear."""
    idx, rows = paged_rows(cache, pos, k_new.shape[1])
    out = dict(cache)
    out["k"] = cache["k"].at[idx].set(k_new.astype(cache["k"].dtype),
                                      mode="drop")
    out["v"] = cache["v"].at[idx].set(v_new.astype(cache["v"].dtype),
                                      mode="drop")
    out["positions"] = cache["positions"].at[idx].set(
        rows.astype(cache["positions"].dtype), mode="drop")
    return out
