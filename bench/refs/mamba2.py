"""Plain reference of a Mamba-2 language model (arXiv:2405.21060, one
group of B/C): per layer a pre-norm RMSNorm, the input projection to
(z, xBC, dt), a causal depthwise convolution of width ``conv_kernel`` with
bias and SiLU over xBC, the selective state-space recurrence

    h_t = exp(dt_t * A) h_{t-1} + dt_t * x_t B_t^T,    y_t = h_t C_t + D x_t

with dt = softplus(dt + dt_bias) and A = -exp(A_log), then the gated
RMSNorm of y * silu(z), the output projection and the residual; a final
RMSNorm and an LM head tied to the embedding.

Straightforward ``jax.numpy`` in float32, token by token (a sequential
scan, not the chunked dual form), one layer at a time. It imports nothing
of the program. It reads the weights the benchmark made, in the layout of
the program's parameter tree (layers stacked under ``blocks``); each weight
passes through ``w(x)`` first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.refs.transformer import rms


def hidden(c: dict, params: dict, tokens, w):
    """Final-normed hidden states (S, d) of one sequence ``tokens`` (S,)."""
    S = tokens.shape[0]
    d_inner = c["expand"] * c["hidden_size"]
    P, N = c["head_dim"], c["state_size"]
    H = d_inner // P
    cw = c["conv_kernel"]
    eps = c["layer_norm_epsilon"]
    x = w(params["embed"])[tokens]

    def layer(x, p):
        u = rms(x, w(p["ln"]), eps) @ w(p["in_proj"])
        z, xbc, dt = (u[:, :d_inner], u[:, d_inner:2 * d_inner + 2 * N],
                      u[:, 2 * d_inner + 2 * N:])
        padded = jnp.concatenate([jnp.zeros((cw - 1, xbc.shape[1])), xbc])
        conv_w = w(p["conv_w"])
        xbc = sum(padded[i:i + S] * conv_w[i] for i in range(cw))
        xbc = jax.nn.silu(xbc + w(p["conv_b"]))
        xs = xbc[:, :d_inner].reshape(S, H, P)
        Bm, Cm = xbc[:, d_inner:d_inner + N], xbc[:, d_inner + N:]
        dt = jax.nn.softplus(dt + w(p["dt_bias"]))                # (S, H)
        A = -jnp.exp(w(p["A_log"]))

        def step(h, inp):
            xt, dtt, bt, ct = inp
            h = (h * jnp.exp(dtt * A)[:, None, None]
                 + dtt[:, None, None] * xt[:, :, None] * bt[None, None, :])
            return h, h @ ct

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (xs, dt, Bm, Cm))
        y = y + w(p["D"])[None, :, None] * xs
        y = rms(y.reshape(S, d_inner) * jax.nn.silu(z), w(p["gnorm"]), eps)
        return x + y @ w(p["out_proj"]), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return rms(x, w(params["final_norm"]), eps)


def head(c: dict, params: dict, h, w):
    """Logits (n, vocab) of hidden rows ``h`` (n, d)."""
    return h @ w(params["embed"]).T
