"""Plain reference of a Qwen2-style decoder (arXiv:2407.10671): pre-norm
RMSNorm, grouped-query attention with biased Q/K/V projections and rotary
embeddings (rotate-half form), SwiGLU MLP, a final RMSNorm and an LM head
tied to the embedding.

Straightforward ``jax.numpy`` in float32, one layer at a time, full causal
attention with no cache, no blocking and no kernels. It imports nothing of
the program. It reads the weights the benchmark made, in the layout of the
program's parameter tree (layers stacked under ``blocks/slot0``); each
weight passes through ``w(x)`` first, which casts it to float32 (or, for
the control, rounds it to a lower precision on the way).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq          # (S, half)
    s = jnp.sin(ang)[:, None].astype(x.dtype)
    c = jnp.cos(ang)[:, None].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def hidden(c: dict, params: dict, tokens, w):
    """Final-normed hidden states (S, d) of one sequence ``tokens`` (S,)."""
    x, _ = _layers(c, params, tokens, w, keep=False)
    return rms(x, w(params["final_norm"]), c["rms_norm_eps"])


def layer_outputs(c: dict, params: dict, tokens, w):
    """Every layer's output (L, S, d) of one sequence ``tokens`` (S,)."""
    return _layers(c, params, tokens, w, keep=True)[1]


def _layers(c: dict, params: dict, tokens, w, keep: bool):
    S = tokens.shape[0]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps = c["rms_norm_eps"]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    x = w(params["embed"])[tokens]

    def layer(x, p):
        a = p["attn"]
        h = rms(x, w(p["ln1"]), eps)
        q = (h @ w(a["wq"]) + w(a["bq"])).reshape(S, H, hd)
        k = (h @ w(a["wk"]) + w(a["bk"])).reshape(S, KV, hd)
        v = (h @ w(a["wv"]) + w(a["bv"])).reshape(S, KV, hd)
        q, k = rope(q, pos, c["rope_theta"]), rope(k, pos, c["rope_theta"])
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        x = x + o.reshape(S, H * hd) @ w(a["wo"])
        m = p["mlp"]
        h = rms(x, w(p["ln2"]), eps)
        x = x + (jax.nn.silu(h @ w(m["w_gate"])) * (h @ w(m["w_up"]))
                 ) @ w(m["w_down"])
        return x, (x if keep else None)

    return jax.lax.scan(layer, x, params["blocks"]["slot0"])


def head(c: dict, params: dict, h, w):
    """Logits (n, vocab) of hidden rows ``h`` (n, d)."""
    return h @ w(params["embed"]).T
