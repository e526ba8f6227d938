"""MTP-masked flash attention — the paper's training hot spot, TPU-native.

The paper (§3.1) precomputes the (n_max·K)² cross-depth mask in HBM and
slices per example. On TPU that costs O(M²) HBM mask traffic per step. This
kernel instead evaluates the *closed-form* predicate

    attend ⇔ (g'=0 ∧ p' ≤ p−g) ∨ (p'−g' = p−g ∧ g' ≤ g)

inside VMEM from two int32 metadata vectors (depth, pos) of length M —
O(M) metadata instead of O(M²) mask bytes (DESIGN.md §3, beyond-paper
optimization; the paper-faithful precompute+slice path lives in
core/masks.py and is what Table-2 benchmarks compare against).

Padding (depth = -1) attends nothing; its output rows are zeroed.

Grid and dataflow mirror flash_attention.py; the metadata vectors ride in
as (block_q, 1) column and (1, block_k) row VMEM tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flash_attention import (softmax_finish, softmax_init,
                                           softmax_scratch, softmax_update)


def _mtp_kernel(qd_ref, qp_ref, kd_ref, kp_ref, q_ref, k_ref, v_ref, o_ref,
                m_scr, l_scr, acc_scr, *, scale: float, n_kv_blocks: int):
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        softmax_init(m_scr, l_scr, acc_scr)

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qg = qd_ref[...]                   # (block_q, 1) depths
    qp = qp_ref[...]                   # rope positions
    kg = kd_ref[...]                   # (1, block_k)
    kp = kp_ref[...]
    anchor_q = qp - qg
    anchor_k = kp - kg
    ok = ((kg == 0) & (kp <= anchor_q)) | ((anchor_k == anchor_q) & (kg <= qg))
    ok &= (qg >= 0) & (kg >= 0)
    softmax_update(s, ok, v, m_scr, l_scr, acc_scr)

    @pl.when(kj == n_kv_blocks - 1)
    def _done():
        o_ref[...] = softmax_finish(l_scr, acc_scr).astype(o_ref.dtype)


def mtp_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  pos: jax.Array, depth: jax.Array, *, scale: float,
                  block_q: int = 128, block_k: int = 128,
                  interpret: bool = False) -> jax.Array:
    """q (B,M,H,hd); k/v (B,M,KV,hd); pos/depth (M,) int32 (-1 pad)."""
    B, M, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    block_q = min(block_q, M)
    block_k = min(block_k, M)
    assert M % block_q == 0 and M % block_k == 0
    n_kv_blocks = M // block_k

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (B, H, M // block_q, n_kv_blocks)

    # metadata enters twice: as (M, 1) columns for the query rows and as
    # (1, M) rows for the key columns, so the mask is a plain 2-D broadcast
    col = pl.BlockSpec((block_q, 1), lambda b, h, i, j: (i, 0))
    row = pl.BlockSpec((1, block_k), lambda b, h, i, j: (0, j))
    out = pl.pallas_call(
        functools.partial(_mtp_kernel, scale=scale, n_kv_blocks=n_kv_blocks),
        grid=grid,
        in_specs=[
            col, col, row, row,
            pl.BlockSpec((None, None, block_q, hd),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, M, hd), q.dtype),
        scratch_shapes=softmax_scratch(block_q, hd),
        interpret=interpret,
    )(depth[:, None], pos[:, None], depth[None, :], pos[None, :], qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
