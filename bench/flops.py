"""Operations and bytes that the algorithm needs, computed from shapes.

One function per model family, read from the configuration file's sizes
(``bench/configs/<config>.json``), never from the program. Bytes are those
of the arrays as they are: the caller passes the weights' ``nbytes`` and
the cache's item size, so a change of dtype moves the count together with
the time. Counted: every matrix multiplication (2 operations per
multiply-add), attention over the keys each query really attends, the
Mamba-2 recurrence. Not counted: norms, activations, softmax, rotary
embeddings and other elementwise work, a small share that only lowers a
roofline or utilisation share.
"""
from __future__ import annotations

# bytes per element, as XLA names its types (copied from the program's
# launch/roofline.py so that no later change there moves this table)
DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
    "float64": 8, "float32": 4, "bfloat16": 2, "float16": 2, "int32": 4,
    "int8": 1,
}


# ---------------------------------------------------------------- families

def transformer_layer_matmul_params(c: dict) -> int:
    d, h, kv, hd, f = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"],
                       c["intermediate_size"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def transformer_flops(c: dict, tokens: int, attended: int,
                      head_rows: int) -> float:
    """Forward of ``tokens`` positions through every layer, ``attended``
    (query, key) pairs summed over those positions, and the LM head over
    ``head_rows`` positions."""
    L = c["num_hidden_layers"]
    h, hd = c["num_attention_heads"], c["head_dim"]
    return (2.0 * L * transformer_layer_matmul_params(c) * tokens
            + 4.0 * L * h * hd * attended
            + 2.0 * c["hidden_size"] * c["vocab_size"] * head_rows)


def transformer_kv_bytes_per_position(c: dict, itemsize: int) -> int:
    return (c["num_hidden_layers"] * 2 * c["num_key_value_heads"]
            * c["head_dim"] * itemsize)


def mamba2_dims(c: dict):
    d_inner = c["expand"] * c["hidden_size"]
    heads = d_inner // c["head_dim"]
    return d_inner, heads, c["head_dim"], c["state_size"]


def mamba2_flops(c: dict, tokens: int, head_rows: int) -> float:
    """Forward of ``tokens`` positions through every SSD layer, token by
    token (the recurrence; the chunked form of a prefill needs no fewer
    useful operations), plus the LM head over ``head_rows`` positions."""
    d = c["hidden_size"]
    d_inner, H, P, N = mamba2_dims(c)
    in_proj = 2 * d * (2 * d_inner + 2 * N + H)
    conv = 2 * c["conv_kernel"] * (d_inner + 2 * N)
    ssm = 2 * H * P * N * 3          # decay, dt*B*x update, C . state
    out_proj = 2 * d_inner * d
    per_token = c["num_hidden_layers"] * (in_proj + conv + ssm + out_proj)
    return per_token * tokens + 2.0 * d * c["vocab_size"] * head_rows


def mamba2_state_bytes(c: dict, conv_itemsize: int) -> int:
    """One slot's recurrent state: float32 SSM state plus the conv window."""
    d_inner, H, P, N = mamba2_dims(c)
    return c["num_hidden_layers"] * (
        H * P * N * 4 + (c["conv_kernel"] - 1) * (d_inner + 2 * N)
        * conv_itemsize)


def target_flops(c: dict, tokens: int, attended: int, head_rows: int) -> float:
    if c["family"] == "dense":
        return transformer_flops(c, tokens, attended, head_rows)
    if c["family"] == "ssm":
        return mamba2_flops(c, tokens, head_rows)
    raise ValueError(f"no FLOP count for family {c['family']!r}")


def drafter_shape(c: dict, drafter: dict) -> dict:
    """The drafter as a transformer configuration: its own width rules
    (``drafter`` keys of the traffic mix) over the target's vocabulary."""
    d = c["hidden_size"]
    heads = max(4, d // 128)
    return {"num_hidden_layers": drafter["layers"], "hidden_size": d,
            "num_attention_heads": heads, "num_key_value_heads": heads,
            "head_dim": d // heads,
            "intermediate_size": max(128, int(3.5 * d) // 128 * 128),
            "vocab_size": c["vocab_size"], "taps": 3}


def drafter_flops(dc: dict, tokens: int, attended: int,
                  head_rows: int) -> float:
    """Drafter forward: tap projection and fusion per position, its blocks,
    and its LM head over ``head_rows`` positions."""
    d = dc["hidden_size"]
    fuse = 2.0 * (dc["taps"] * d * d + 2 * d * d) * tokens
    return fuse + transformer_flops(dc, tokens, attended, head_rows)


# ---------------------------------------------------------------- serving

def decode_iteration(c: dict, live: list, weight_bytes: int,
                     cache_itemsize: int, conv_itemsize: int) -> tuple:
    """(operations, bytes) one decode iteration needs over the active slots,
    whose committed lengths are ``live``: the target's forward over one new
    position per slot, attending its committed positions and itself, and
    one LM-head row each. Bytes: every weight once, each slot's live cache
    positions read once and one new position written, and a recurrent
    target's state read and written."""
    B = len(live)
    flops = target_flops(c, B, sum(n + 1 for n in live), B)
    if c["family"] == "dense":
        per_pos = transformer_kv_bytes_per_position(c, cache_itemsize)
        state = 0
    else:
        per_pos = 0
        state = 2 * B * mamba2_state_bytes(c, conv_itemsize)
    nbytes = weight_bytes + per_pos * (sum(live) + B) + state
    return flops, nbytes


def prefill_attended(n: int) -> int:
    """(query, key) pairs of a causal prefill of ``n`` tokens."""
    return n * (n + 1) // 2
