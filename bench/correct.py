"""The comparison that decides ``correct`` for a served model, and its
control.

For each sampled request the reference runs once over the prompt followed
by the served tokens (teacher forcing) and reads, at every position that
produced a served token, how far that token's logit lies below the
reference's best logit there. The number compared is the widest such gap
over the sample. A greedy server that computes what the reference computes
reads 0 up to rounding; a wrong token reads the distance to the right one.

The control is the reference itself with its weights rounded to float8
(e4m3, one scale per weight matrix), the next precision below the
configuration's bfloat16 and the step a later change might take: at the
same positions it reads the gap of the token the rounded model puts first.
It runs in ``bench/control.py`` and the tests, never in a benchmark run.

Everything here runs after the window, once the program's state is freed;
one sequence at a time, padded to one length so one program serves all.
"""
from __future__ import annotations

import importlib
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HEAD_ROWS = 256            # logits rows computed at once
E4M3_MAX = 448.0


def load_reference(name: str):
    """The reference module ``bench/refs/<name>.py``."""
    return importlib.import_module(f"bench.refs.{name}")


def as_f32(x):
    return x.astype(jnp.float32)


def round_e4m3(x):
    """Round float32 ``x`` to the nearest float8 e4m3 value (3 mantissa
    bits, smallest normal exponent -6), ties to even; |x| <= 448."""
    mag = jnp.abs(x)
    e = jnp.floor(jnp.log2(jnp.maximum(mag, 2.0 ** -9)))
    step = 2.0 ** (jnp.maximum(e, -6.0) - 3.0)
    return jnp.clip(jnp.round(x / step) * step, -E4M3_MAX, E4M3_MAX)


def as_e4m3(x):
    """float32 value of ``x`` after a round trip through float8 e4m3, with
    one scale per matrix that maps its largest magnitude to 448. Vectors
    (norm scales, biases, SSM parameters) stay float32."""
    x = x.astype(jnp.float32)
    if x.ndim < 2:
        return x
    scale = jnp.max(jnp.abs(x)) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return round_e4m3(x / scale) * scale


def _pad(seq: np.ndarray, length: int) -> np.ndarray:
    out = np.zeros((length,), np.int32)
    out[:len(seq)] = seq
    return out


def served_gaps(ref, cfg: dict, params, samples: Sequence[Tuple[np.ndarray,
                                                                np.ndarray]],
                pad_len: int, control: bool = False) -> List[np.ndarray]:
    """Per sample (prompt, served tokens): the gap, at each served token, of
    that token's reference logit below the reference's best logit; with
    ``control`` the gap of the token the float8 reference puts first
    instead. Causal, so the padding after each sequence changes nothing."""

    @jax.jit
    def run(params, tokens, rows, served):
        h = ref.hidden(cfg, params, tokens, as_f32)
        hc = ref.hidden(cfg, params, tokens, as_e4m3) if control else None
        gaps = []
        for i in range(0, rows.shape[0], HEAD_ROWS):
            r = rows[i:i + HEAD_ROWS]
            lg = ref.head(cfg, params, h[r], as_f32)
            best = lg.max(-1)
            if control:
                pick = jnp.argmax(ref.head(cfg, params, hc[r], as_e4m3), -1)
            else:
                pick = served[i:i + HEAD_ROWS]
            gaps.append(best - jnp.take_along_axis(lg, pick[:, None], 1)[:, 0])
        return jnp.concatenate(gaps)

    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, served in samples:
            seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
            if len(seq) > pad_len:
                raise ValueError(f"sequence of {len(seq)} > {pad_len}")
            n = len(served)
            rows = np.full((pad_len,), len(prompt) - 1, np.int32)
            rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
            tgt = np.zeros((pad_len,), np.int32)
            tgt[:n] = served
            g = run(params, jnp.asarray(_pad(seq, pad_len)),
                    jnp.asarray(rows), jnp.asarray(tgt))
            out.append(np.asarray(g)[:n])
    return out


def choose_sample(finished: Sequence[Tuple[np.ndarray, np.ndarray]],
                  min_tokens: int, seed: int) -> List[int]:
    """Indices of a sample of ``finished`` drawn from ``seed``: the request
    with the most served tokens, then others in a seeded order until the
    sample holds ``min_tokens`` served tokens or every request."""
    if not finished:
        return []
    lens = [len(s) for _, s in finished]
    longest = int(np.argmax(lens))
    order = [i for i in np.random.default_rng(seed).permutation(len(finished))
             if i != longest]
    pick, total = [longest], lens[longest]
    for i in order:
        if total >= min_tokens:
            break
        pick.append(int(i))
        total += lens[i]
    return pick
