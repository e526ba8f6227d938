"""Mamba-2 language models (SSD layers, one group of B/C): no attention, a
per-slot recurrent state.

Counted: every matrix multiplication, the causal convolution and the
recurrence, token by token (the chunked form of a prefill needs no fewer
useful operations). A decode step reads every weight once and reads and
writes each slot's state. The layer's own parameters get their
initialisers' scales (``weight``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def program_pairs(cfg: dict, tcfg) -> dict:
    """File key -> the program's value, compared size for size."""
    return {"hidden_size": tcfg.d_model,
            "num_hidden_layers": tcfg.n_layers,
            "state_size": tcfg.ssm.d_state,
            "expand": tcfg.ssm.expand,
            "head_dim": tcfg.ssm.head_dim,
            "conv_kernel": tcfg.ssm.conv_width,
            "vocab_size": tcfg.vocab_size,
            "layer_norm_epsilon": tcfg.norm_eps,
            "tie_word_embeddings": tcfg.tie_embeddings}


def dims(c: dict):
    d_inner = c["expand"] * c["hidden_size"]
    heads = d_inner // c["head_dim"]
    return d_inner, heads, c["head_dim"], c["state_size"]


def flops(c: dict, past: int, new: int, head_rows: int) -> float:
    """One sequence's forward over ``new`` positions (the state carries
    ``past``: no more work per position), with ``head_rows`` LM-head
    rows."""
    d = c["hidden_size"]
    d_inner, H, P, N = dims(c)
    in_proj = 2 * d * (2 * d_inner + 2 * N + H)
    conv = 2 * c["conv_kernel"] * (d_inner + 2 * N)
    ssm = 2 * H * P * N * 3          # decay, dt*B*x update, C . state
    out_proj = 2 * d_inner * d
    per_token = c["num_hidden_layers"] * (in_proj + conv + ssm + out_proj)
    return per_token * new + 2.0 * d * c["vocab_size"] * head_rows


def state_bytes(c: dict, cache_itemsize: int) -> int:
    """One slot's recurrent state: float32 SSM state plus the conv window,
    kept in the cache's dtype."""
    d_inner, H, P, N = dims(c)
    return c["num_hidden_layers"] * (
        H * P * N * 4 + (c["conv_kernel"] - 1) * (d_inner + 2 * N)
        * cache_itemsize)


def decode_bytes(c: dict, live: list, weight_bytes: int,
                 cache_itemsize: int) -> int:
    """One decode iteration over ``len(live)`` slots: every weight once,
    each slot's state read and written."""
    return weight_bytes + 2 * len(live) * state_bytes(c, cache_itemsize)


def weight(name: str, key, shape, dtype):
    """The SSD layer's own leaves; None for a leaf of the common rules
    (``bench/weights.py``):

      ``A_log``     log U(1, 16)
      ``dt_bias``   softplus^-1 of log-uniform dt in [1e-3, 1e-1]
      ``D``         1
      ``conv_w``    N(0, 0.5^2)
      ``conv_b``    N(0, 0.02^2)
    """
    f32 = jnp.float32
    if name == "D":
        return jnp.ones(shape, dtype)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)
                       ).astype(dtype)
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, jnp.log(1e-3),
                                        jnp.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    std = {"conv_w": 0.5, "conv_b": 0.02}.get(name)
    if std is None:
        return None
    return (std * jax.random.normal(key, shape, f32)).astype(dtype)
