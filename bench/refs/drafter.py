"""Plain reference of one P-EAGLE drafter training step (the paper's
whole-sequence MTP training, arXiv:2602.01469 §2-§3): the frozen target's
hidden-state taps, the drafter's multi-token-prediction forward over the
COD-expanded positions, the depth-weighted cross-entropy, its gradient,
global-norm clipping and the AdamW update with a linear warm-up schedule.

Straightforward ``jax.numpy`` in float32 with full (unblocked) masked
attention. It imports nothing of the program; the target's layers come
from ``bench/refs/transformer.py``. The drafter's parameters are read in
the layout of the program's tree (blocks stacked under ``blocks``), and
the batch is the timed path's: tokens (B, n) and the expanded positions,
depths and labels (B, M), padded with -1.

Conventions (the paper's, as its §2 states them): taps are the outputs of
target layers 2, L/2 and L-1 concatenated; drafter position p pairs
fc(taps[p]) with the embedding of token p+1 at depth 0, and the shared
hidden vector with the mask token (the top id) at depth g > 0; position
(g, p) attends (0, p') for p' <= p - g and its own chain (g', p - g + g')
for g' <= g; it predicts its label; rotary positions are p with theta
``rope_theta``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.refs.transformer import rms, rope


def target_taps(tref, c: dict, tparams: dict, tokens, dtype=jnp.float32):
    """(n, 3 d) taps of one sequence: outputs of layers 2, L/2, L-1 of the
    target reference ``tref``, computed in ``dtype``."""
    L = c["num_hidden_layers"]
    outs = tref.layer_outputs(c, tparams, tokens, lambda w: w.astype(dtype))
    return jnp.concatenate([outs[i] for i in (2, L // 2, L - 1)], axis=-1)


def mtp_logits(d: dict, p: dict, tokens, taps, pos, depth, vocab: int):
    """Drafter logits (M, vocab) of one sequence's expanded positions."""
    n = tokens.shape[0]
    H, hd = d["num_attention_heads"], d["head_dim"]
    eps = d["rms_norm_eps"]
    safe = jnp.clip(pos, 0, n - 1)
    fc = taps @ p["fc"]
    hid = jnp.where((depth == 0)[:, None], fc[safe], p["h_shared"][None])
    tok = jnp.where(depth == 0, tokens[jnp.clip(safe + 1, 0, n - 1)],
                    vocab - 1)
    x = jnp.concatenate([p["embed"][tok], hid], -1) @ p["fuse"]
    anchor = pos - depth
    valid = depth >= 0
    see = (((depth[None] == 0) & (pos[None] <= anchor[:, None]))
           | ((anchor[None] == anchor[:, None])
              & (depth[None] <= depth[:, None])))
    see &= valid[:, None] & valid[None]
    rp = jnp.maximum(pos, 0)
    M = pos.shape[0]

    def layer(x, b):
        a = b["attn"]
        h = rms(x, b["ln1"], eps)
        q = rope((h @ a["wq"]).reshape(M, H, hd), rp, d["rope_theta"])
        k = rope((h @ a["wk"]).reshape(M, H, hd), rp, d["rope_theta"])
        v = (h @ a["wv"]).reshape(M, H, hd)
        s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
        s = jnp.where(see[None], s, -jnp.inf)
        w = jax.nn.softmax(s, -1)
        w = jnp.where(see[None], w, 0.0)         # padding rows see nothing
        o = jnp.einsum("hqk,khd->qhd", w, v).reshape(M, H * hd)
        x = x + o @ a["wo"]
        m = b["mlp"]
        h = rms(x, b["ln2"], eps)
        return x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])
                    ) @ m["w_down"], None

    x, _ = jax.lax.scan(layer, x, p["blocks"])
    return rms(x, p["final_norm"], eps) @ p["lm_head"]


def loss(d: dict, p: dict, batch_taps, batch, vocab: int):
    """Mean cross-entropy over the valid expanded positions of the batch
    (every depth weighted 1)."""
    tokens, pos, depth, labels = batch
    total, count = 0.0, 0.0
    for b in range(tokens.shape[0]):
        lg = mtp_logits(d, p, tokens[b], batch_taps[b], pos[b], depth[b],
                        vocab)
        ok = (labels[b] >= 0) & (depth[b] >= 0)
        ce = -jnp.take_along_axis(jax.nn.log_softmax(lg, -1),
                                  jnp.maximum(labels[b], 0)[:, None], 1)[:, 0]
        total = total + jnp.sum(jnp.where(ok, ce, 0.0))
        count = count + jnp.sum(ok)
    return total / jnp.maximum(count, 1.0)


def lr_at(step, opt: dict):
    """Linear warm-up to ``lr`` over max(total * ratio, 1) steps, then a
    linear decay to 0 at ``total_steps``."""
    total = opt["total_steps"]
    warm = max(int(total * opt["warmup_ratio"]), 1)
    s = jnp.asarray(step, jnp.float32)
    return jnp.where(s < warm, opt["lr"] * s / warm,
                     opt["lr"] * jnp.maximum(total - s, 0.0)
                     / max(total - warm, 1))


def adamw(p, m, v, g, step, opt: dict):
    """Clip ``g`` to global norm ``max_grad_norm``, then one decoupled-
    weight-decay Adam step (step counted from 1). Returns (p, m, v, the
    clipped gradient)."""
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(
        1.0, opt["max_grad_norm"] / jnp.maximum(norm, 1e-9)), g)
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    lr = lr_at(step, opt)
    p = jax.tree.map(lambda p, m, v: p - lr * (
        (m / bc1) / (jnp.sqrt(v / bc2) + eps) + opt["weight_decay"] * p),
        p, m, v)
    return p, m, v, g
