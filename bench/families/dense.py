"""Dense decoder-only transformers (Qwen2-style): grouped-query attention
over every earlier position in every layer, a gated MLP, an LM head.

Counted: every matrix multiplication (2 operations per multiply-add) and
attention over the (query, key) pairs each position really attends. A
decode step reads every weight once, each live cache position once and
writes one new position per slot.
"""
from __future__ import annotations


def program_pairs(cfg: dict, tcfg) -> dict:
    """File key -> the program's value, compared size for size."""
    return {"hidden_size": tcfg.d_model,
            "num_hidden_layers": tcfg.n_layers,
            "num_attention_heads": tcfg.n_heads,
            "num_key_value_heads": tcfg.n_kv_heads,
            "head_dim": tcfg.head_dim,
            "intermediate_size": tcfg.d_ff,
            "vocab_size": tcfg.vocab_size,
            "rope_theta": tcfg.rope_theta,
            "rms_norm_eps": tcfg.norm_eps,
            "tie_word_embeddings": tcfg.tie_embeddings,
            "attention_bias": tcfg.qkv_bias}


def layer_matmul_params(c: dict) -> int:
    d, h, kv, hd, f = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"],
                       c["intermediate_size"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def causal_pairs(past: int, new: int) -> int:
    """(query, key) pairs of ``new`` causal positions after ``past`` cached
    ones: position ``past + i`` attends ``past + i + 1`` keys."""
    return new * past + new * (new + 1) // 2


def flops(c: dict, past: int, new: int, head_rows: int) -> float:
    """One sequence's forward over ``new`` positions after ``past`` cached
    ones through every layer, attention over their causal (query, key)
    pairs, and the LM head over ``head_rows`` positions."""
    L = c["num_hidden_layers"]
    h, hd = c["num_attention_heads"], c["head_dim"]
    return (2.0 * L * layer_matmul_params(c) * new
            + 4.0 * L * h * hd * causal_pairs(past, new)
            + 2.0 * c["hidden_size"] * c["vocab_size"] * head_rows)


def kv_bytes_per_position(c: dict, itemsize: int) -> int:
    return (c["num_hidden_layers"] * 2 * c["num_key_value_heads"]
            * c["head_dim"] * itemsize)


def decode_bytes(c: dict, live: list, weight_bytes: int,
                 cache_itemsize: int) -> int:
    """One decode iteration over slots of committed lengths ``live``: every
    weight once, each slot's live positions read and one new one
    written."""
    return (weight_bytes + kv_bytes_per_position(c, cache_itemsize)
            * (sum(live) + len(live)))
