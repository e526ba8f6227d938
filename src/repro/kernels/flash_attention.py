"""Flash attention for TPU (pl.pallas_call + BlockSpec VMEM tiling).

Target-model attention hot spot: causal or sliding-window, optional logit
softcap (gemma2), GQA via a grouped-query layout. Online softmax with
float32 VMEM scratch accumulators; K/V stream through VMEM in (block_k, hd)
tiles while a (block_q, hd) query tile stays resident — the classic
HBM→VMEM dataflow for the MXU.

Grid: (batch, q_heads, Sq/block_q, Skv/block_k); the innermost grid
dimension iterates KV blocks for a fixed query tile, accumulating into
scratch, and writes the output tile on the last iteration.

The online-softmax state helpers (``softmax_*``) are shared with the MTP
and decode kernels. Validated on CPU with interpret=True against
kernels/ref.py (the same math as models/layers.blocked_attention), and
compiled for a described v5e by tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128                        # vector lanes of one TPU vreg


def softmax_scratch(rows: int, hd: int) -> list:
    """VMEM scratch for the online softmax of ``rows`` query rows: running
    max and denominator as (rows, 128) lane-replicated tiles, plus the
    (rows, hd) float32 accumulator. Mosaic lays out 2-D tiles only, so the
    per-row statistics are kept lane-broadcast rather than 1-D."""
    return [pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32)]


def softmax_init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def softmax_update(s, ok, v, m_scr, l_scr, acc_scr):
    """Fold one (rows, block_k) masked score tile and its (block_k, hd)
    values into the running softmax state."""
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_scr[...]                                   # (rows, LANES)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    # mask p explicitly: fully-masked rows would see exp(-inf - -inf) = 1
    p = jnp.where(ok, jnp.exp(s - m_new[:, :1]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def softmax_finish(l_scr, acc_scr):
    """Normalized output rows; rows that attended nothing are zero."""
    l = l_scr[...][:, :1]
    out = acc_scr[...] / jnp.maximum(l, 1e-30)
    return jnp.where(l > 0, out, 0.0)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, softcap: float,
                  block_q: int, block_k: int, n_kv_blocks: int,
                  kv_len: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        softmax_init(m_scr, l_scr, acc_scr)

    q = q_ref[...].astype(jnp.float32)            # (block_q, hd)
    k = k_ref[...].astype(jnp.float32)            # (block_k, hd)
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    ok = k_pos < kv_len                # mask pad-to-block keys
    if causal:
        ok &= q_pos >= k_pos
    if window > 0:
        ok &= (q_pos - k_pos) < window
    softmax_update(s, ok, v, m_scr, l_scr, acc_scr)

    @pl.when(kj == n_kv_blocks - 1)
    def _done():
        o_ref[...] = softmax_finish(l_scr, acc_scr).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, kv_len: int = 0,
                    interpret: bool = False) -> jax.Array:
    """q (B, Sq, H, hd); k/v (B, Skv, KV, hd), H % KV == 0.

    Sq/Skv must be multiples of block_q/block_k (ops.py pads); ``kv_len``
    marks the number of real (unpadded) keys (0 => all)."""
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    kv_len = kv_len or Skv
    G = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    n_kv_blocks = Skv // block_k

    qt = q.transpose(0, 2, 1, 3)                  # (B, H, Sq, hd)
    kt = k.transpose(0, 2, 1, 3)                  # (B, KV, Skv, hd)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, Sq // block_q, n_kv_blocks)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, block_q=block_q,
                          block_k=block_k, n_kv_blocks=n_kv_blocks,
                          kv_len=kv_len),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=softmax_scratch(block_q, hd),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
