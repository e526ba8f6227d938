"""Operations and bytes that the algorithm needs, computed from shapes.

Read from the configuration file's sizes (``bench/configs/<config>.json``),
never from the program. What differs by model family is the family
module's (``bench/families/<family>.py``: ``flops`` and ``decode_bytes``);
callers give each sequence as ``(past, new)``, ``new`` positions after
``past`` cached ones, so a family counts the (query, key) pairs its own
layers attend. Bytes are those of the arrays as they are: the caller
passes the weights' ``nbytes`` and the cache's item size, so a change of
dtype moves the count together with the time. Counted: every matrix
multiplication (2 operations per multiply-add), attention over the keys
each query really attends, a recurrence's state update. Not counted:
norms, activations, softmax, rotary embeddings and other elementwise work,
a small share that only lowers a roofline or utilisation share.
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


def target_flops(family, c: dict, seqs, head_rows: int) -> float:
    """The target's forward over sequences ``seqs``, each ``(past, new)``,
    with ``head_rows`` LM-head rows each."""
    return sum(family.flops(c, past, new, head_rows) for past, new in seqs)


def decode_iteration(family, c: dict, live: list, weight_bytes: int,
                     cache_itemsize: int) -> tuple:
    """(operations, bytes) one decode iteration needs over the active slots,
    whose committed lengths are ``live``: the target's forward over one new
    position per slot after its committed ones, and one LM-head row each;
    the bytes the family reads and writes for it."""
    flops = target_flops(family, c, [(n, 1) for n in live], 1)
    return flops, family.decode_bytes(c, live, weight_bytes, cache_itemsize)


# ---------------------------------------------------------------- drafter

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
    """Drafter forward: tap projection and fusion per position, its blocks
    (a dense transformer's: attention and gated MLP matmuls, and the
    ``attended`` (query, key) pairs its MTP mask admits), and its LM head
    over ``head_rows`` positions."""
    d, L = dc["hidden_size"], dc["num_hidden_layers"]
    h, kv, hd, f = (dc["num_attention_heads"], dc["num_key_value_heads"],
                    dc["head_dim"], dc["intermediate_size"])
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    fuse = 2.0 * (dc["taps"] * d * d + 2 * d * d) * tokens
    return fuse + (2.0 * L * layer * tokens + 4.0 * L * h * hd * attended
                   + 2.0 * d * dc["vocab_size"] * head_rows)
