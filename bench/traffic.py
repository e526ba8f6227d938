"""The one traffic generator: a mix file of parameters in, a request plan
out, the same plan for the same ``--seed``.

Every seed gets the same multiset of sizes: stratified quantiles of the
mix's distributions, dealt in an order drawn from the seed. Only the order and the prompt tokens
change with the seed, so runs on different seeds do the same amount of
work. This keeps the long-tail idea of the program's own benchmark mixes
(``benchmarks/common.longtail_budgets``: many short requests, a few long
ones) but draws the tail from a log-uniform law instead of a fixed ratio.

Mix keys read here (``bench/traffic/<mix>.json``):

  loop            "closed": ``clients`` callers, each sending its next
                  request when the previous one ends (the only loop).
  prompt_len      [lo, hi] tokens, log-uniform.
  output_len      [lo, hi] tokens (the request's ``max_new_tokens``),
                  log-uniform.
  prompt_sizes    how many distinct prompt lengths the grid holds; each is
                  warmed up before the window, so none compiles inside it.
  cycle           requests per closed-loop client before its list repeats.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass(frozen=True)
class PlannedRequest:
    prompt: np.ndarray        # int32 token ids
    max_new: int
    client: int               # the closed-loop client that sends it


def loguniform_grid(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` stratified quantiles of the log-uniform law on [lo, hi],
    rounded to whole tokens (midpoints of ``n`` equal-probability bins)."""
    if not 1 <= lo <= hi:
        raise ValueError(f"bad range [{lo}, {hi}]")
    u = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
                   ).astype(np.int64)


def prompt_lengths(mix: dict) -> List[int]:
    """The distinct prompt lengths the mix can send (warm-up covers all)."""
    lo, hi = mix["prompt_len"]
    return sorted(set(loguniform_grid(lo, hi, mix["prompt_sizes"]).tolist()))


def plan(mix: dict, seed: int, vocab: int, n_requests: int) -> List[PlannedRequest]:
    """``n_requests`` requests of the mix for ``seed``, dealt to its
    clients in turn, in each client's order. Prompt ids are uniform over
    ``[0, vocab - 2)`` (the top id is the drafter's mask token in the
    program's speculative modes)."""
    rng = np.random.default_rng(seed)
    plens = np.asarray(prompt_lengths(mix))
    n = max(n_requests, 1)
    prompts = np.resize(plens, n)                  # same multiset per seed
    outs = loguniform_grid(*mix["output_len"], n)
    rng.shuffle(prompts)
    rng.shuffle(outs)
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    owner = np.arange(n) % mix["clients"]
    return [PlannedRequest(rng.integers(0, vocab - 2, size=int(p),
                                        dtype=np.int32), int(o), int(c))
            for p, o, c in zip(prompts, outs, owner)]


def stagger(mix: dict, client: int, max_new: int) -> int:
    """Closed loop: the first request of ``client`` is cut to a share of its
    budget, ``(client + 0.5) / clients``, so the clients start out of phase
    and do not all finish together."""
    return max(1, int(max_new * (client + 0.5) / mix["clients"]))
