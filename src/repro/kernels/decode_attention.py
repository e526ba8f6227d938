"""Split-K decode attention (flash-decode) for TPU.

Serving hot spot: a tiny query block (the K+1 speculative verify tokens, or
the K parallel draft slots) against a long KV cache. The sequence dimension
is split across grid steps; each step reduces a (block_k, hd) cache tile
against the resident (T, hd) query tile with online-softmax scratch.

Cache slots carry absolute positions (-1 = empty) so ring (sliding-window)
caches and speculative invalidation mask correctly — the same convention as
models/layers.make_kv_cache.

``paged_decode_attention`` is the paged-KV twin (serving/cache_ops paged
layout): K/V live in a shared pool of fixed-size position pages and each
batch row owns a block table. The page id is scalar-prefetched into the
BlockSpec index map, so every grid step DMAs one page straight from the
pool — the gather happens in the index stream, and not even the one-layer
view the engine's jnp path reads (models/layers.paged_view) exists in HBM.
Unallocated table entries (-1) are masked in-kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (softmax_finish, softmax_init,
                                           softmax_scratch, softmax_update)


def _decode_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, window: int,
                   n_kv_blocks: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        softmax_init(m_scr, l_scr, acc_scr)

    q = q_ref[...].astype(jnp.float32)           # (T, hd)
    k = k_ref[...].astype(jnp.float32)           # (block_k, hd)
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qp = qpos_ref[...]                           # (T, 1)
    kp = kpos_ref[...]                           # (1, block_k)
    ok = (kp <= qp) & (kp >= 0)
    if window > 0:
        ok &= (qp - kp) < window
    softmax_update(s, ok, v, m_scr, l_scr, acc_scr)

    @pl.when(kj == n_kv_blocks - 1)
    def _done():
        o_ref[...] = softmax_finish(l_scr, acc_scr).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     k_positions: jax.Array, q_positions: jax.Array, *,
                     scale: float, window: int = 0, block_k: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q (B,T,H,hd) small T; k/v (B,S,KV,hd); k_positions (B,S) int32;
    q_positions (B,T) int32."""
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    block_k = min(block_k, S)
    assert S % block_k == 0
    n_kv_blocks = S // block_k

    qt = q.transpose(0, 2, 1, 3)                 # (B, H, T, hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (B, H, n_kv_blocks)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, window=window,
                          n_kv_blocks=n_kv_blocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, T, 1), lambda b, h, j: (b, 0, 0)),
            pl.BlockSpec((None, 1, block_k), lambda b, h, j: (b, 0, j)),
            pl.BlockSpec((None, None, T, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, T, hd),
                               lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        scratch_shapes=softmax_scratch(T, hd),
        interpret=interpret,
    )(q_positions[:, :, None], k_positions[:, None, :], qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# paged-KV decode attention (block-table gather in the index stream)
# ---------------------------------------------------------------------------

def _paged_kernel(bt_ref, qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, window: int,
                  n_pages: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        softmax_init(m_scr, l_scr, acc_scr)

    q = q_ref[...].astype(jnp.float32)           # (T, hd)
    k = k_ref[...].astype(jnp.float32)           # (page, hd)
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qp = qpos_ref[...]                           # (T, 1)
    kp = kpos_ref[...]                           # (1, page)
    ok = (kp <= qp) & (kp >= 0)
    if window > 0:
        ok &= (qp - kp) < window
    # unallocated page: the index map clamped it to page 0, whose positions
    # could alias a *live* request's — mask the whole contribution
    ok &= bt_ref[b, j] >= 0
    softmax_update(s, ok, v, m_scr, l_scr, acc_scr)

    @pl.when(j == n_pages - 1)
    def _done():
        o_ref[...] = softmax_finish(l_scr, acc_scr).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, pos_pool: jax.Array,
                           block_table: jax.Array, q_positions: jax.Array, *,
                           scale: float, window: int = 0,
                           interpret: bool = False) -> jax.Array:
    """q (B,T,H,hd) small T; k_pool/v_pool (NP, page, KV, hd) shared page
    pool; pos_pool (NP, page) int32 absolute positions (-1 = empty);
    block_table (B, nb) int32 page ids (-1 = unallocated); q_positions
    (B,T) int32. Each batch row attends only to the pages its table names —
    one pool-resident page per grid step, no per-slot contiguous copy."""
    B, T, H, hd = q.shape
    NP, page, KV = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    nb = block_table.shape[1]
    G = H // KV

    qt = q.transpose(0, 2, 1, 3)                 # (B, H, T, hd)
    grid = (B, H, nb)
    # the pools enter as (NP, page, KV*hd), a free reshape: a (page, hd)
    # block at column block h // G is one KV head of one page, and both of
    # its minor dims are tile-aligned (a squeezed KV axis would not be)

    def page_idx(b, h, j, bt):
        return jnp.maximum(bt[b, j], 0)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, T, 1), lambda b, h, j, bt: (b, 0, 0)),
            pl.BlockSpec((None, 1, page),
                         lambda b, h, j, bt: (page_idx(b, h, j, bt), 0, 0)),
            pl.BlockSpec((None, None, T, hd),
                         lambda b, h, j, bt: (b, h, 0, 0)),
            pl.BlockSpec((None, page, hd),
                         lambda b, h, j, bt, G=G:
                         (page_idx(b, h, j, bt), 0, h // G)),
            pl.BlockSpec((None, page, hd),
                         lambda b, h, j, bt, G=G:
                         (page_idx(b, h, j, bt), 0, h // G)),
        ],
        out_specs=pl.BlockSpec((None, None, T, hd),
                               lambda b, h, j, bt: (b, h, 0, 0)),
        scratch_shapes=softmax_scratch(T, hd),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, window=window,
                          n_pages=nb),
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        interpret=interpret,
    )(block_table, q_positions[:, :, None], pos_pool[:, None, :], qt,
      k_pool.reshape(NP, page, KV * hd), v_pool.reshape(NP, page, KV * hd))
    return out.transpose(0, 2, 1, 3)
