"""Batched speculative-decoding engine (the framework's vLLM analogue).

Static-shape, jit-compiled draft→verify→commit iterations over a fixed batch
of request slots. The request-lifecycle layer on top — per-slot admission
into a live batch, immediate slot free on EOS/budget, per-request metrics —
is serving/scheduler.py; this module supplies the per-slot primitives
(``prefill_into_slot``, ``free_slot``, ``step`` with an active mask).
Three drafter modes:

  "parallel" — P-EAGLE: one drafter forward drafts K tokens (paper §2/§5.3)
  "ar"       — AR EAGLE-3 baseline: K sequential drafter forwards
  "none"     — vanilla autoregressive decoding (1 target forward per token)

Verification policy is PER REQUEST (serving/sampling.py): every slot
carries its own ``SamplingParams`` row — temperature / top-k / top-p and a
deterministic PRNG stream derived from the request's seed — and one jitted
step runs greedy prefix matching for ``temperature == 0`` rows and seeded
lossless rejection sampling against the row-warped target distribution for
the rest (core/spec_decode.mixed_verify). Greedy rows + "parallel"/"ar"
reproduce target-greedy output exactly, and sampled rows are a pure
function of ``(seed, committed prefix)`` — the losslessness and
determinism property tests rely on both. There is no engine-global
verification RNG.

Model sharding (``EngineConfig(shard_model=True)``) spreads the engine's
resident state — weights and full-length KV, contiguous rows or page pools
alike — over a 1-D ``("model",)`` device mesh while the scheduler's host
loop is unchanged. Every jitted entry point carries explicit NamedSharding
in/out shardings, and each compute step gathers the sharded storage at a
replication boundary (sharding/utils.replicate_tree) before running
bit-identically to the single-device engine; see docs/sharding.md for the
losslessness argument and layout table.
"""
from __future__ import annotations

import functools
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import DrafterConfig, ModelConfig
from repro.core import drafter as D
from repro.core import spec_decode as SD
from repro.models import get_model
from repro.serving import cache_ops
from repro.serving.prefix_cache import PrefixCache
from repro.serving.sampling import (SamplingParams, batch_sampling_state,
                                    blank_sampling_state, draft_keys,
                                    sampling_state_sds, step_keys)
from repro.sharding import rules as shard_rules
from repro.sharding.utils import replicate_tree, serving_mesh
from repro.tracing import span

Array = jax.Array


@dataclass(frozen=True)
class EngineConfig:
    """Static configuration of a serving :class:`Engine`.

    Attributes:
      K: speculation depth — tokens drafted per iteration (ignored when
        ``drafter_mode == "none"``).
      max_new_tokens: default per-request generation budget; the scheduler
        may override it per request (``Request.max_new_tokens`` /
        ``SamplingParams.max_new_tokens``).
      sampling: default :class:`SamplingParams` for slots/requests that do
        not carry their own (whole-batch ``prefill``/``run``, and
        ``Request``s without an explicit policy). The default is greedy
        verification — token-for-token lossless vs target-greedy decoding.
      greedy: DEPRECATED alias (emits ``DeprecationWarning``): ``True``
        constructs ``SamplingParams.greedy()``, ``False`` a temperature-1.0
        seeded ``SamplingParams``. Pass per-request ``SamplingParams``
        instead.
      drafter_mode: "parallel" (P-EAGLE), "ar" (EAGLE-3 baseline) or "none"
        (vanilla AR decoding, one target forward per token).
      cache_dtype: KV/state cache dtype ("bfloat16" on accelerators).
      max_len: total cache positions per slot (prompt + generation + K+1
        speculative overshoot must fit).
    """
    K: int = 5                       # speculation depth (drafted tokens/iter)
    max_new_tokens: int = 64
    greedy: Optional[bool] = None    # DEPRECATED → sampling (see below)
    drafter_mode: str = "parallel"   # parallel | ar | none
    cache_dtype: str = "float32"     # bfloat16 on accelerators
    max_len: int = 512               # total positions per slot
    # --- KV layout -------------------------------------------------------
    # "contiguous": every slot owns a max_len cache row (the baseline).
    # "paged": full-length attention KV lives in a shared pool of fixed-size
    # position pages behind per-slot block tables (cache_ops); admission
    # allocates ceil(need/page_size) pages instead of a max_len row.
    kv_layout: str = "contiguous"
    page_size: int = 16              # positions per page (paged layout)
    pool_pages: int = 0              # pool size; 0 = batch * max_len/page_size
    # "incremental": admission claims only the pages the prompt (plus one
    # speculative block) occupies; `ensure_capacity` grows the slot's
    # allocation page-by-page as decode crosses page boundaries, so the pool
    # holds requests by their *current* length, not their worst case.
    # "upfront": PR-2 behavior — admission reserves prompt+budget+overshoot
    # for the request's whole lifetime (the static-admission baseline
    # benchmarks/table13_async.py compares against).
    kv_growth: str = "incremental"
    # Cross-request prefix caching (serving/prefix_cache.py): pages of
    # committed prompt/generation streams stay indexed by token-prefix
    # chain after their request finishes (or is preempted), and admission
    # of a request whose prompt walks a cached chain maps those pages into
    # its block-table row — prefilling only the uncached suffix — instead
    # of recomputing them. Paged-only. Dense attention targets take the
    # fast path; recurrent families (ssm/hybrid) carry per-slot state no
    # page holds, so they serve unchanged with the cache structurally
    # idle. A hit is token-for-token lossless vs a cold prefill
    # (tests/test_prefix_cache.py), and cached pages are reclaimed LRU
    # under pool pressure (pages live slots map are pinned).
    prefix_cache: bool = False
    # Power-of-two bucketing for per-slot admission prefills, so a stream of
    # distinct prompt lengths compiles O(log2 max_len) traces instead of one
    # per length. Append-only attention families right-pad to the bucket
    # (pads are causally inert; their cache entries are invalidated);
    # recurrent families (ssm/hybrid) and targets with ring sliding-window
    # KV — where pads would corrupt the recurrence / wrap over live window
    # entries — split the prompt into its MSB-first power-of-two chunks.
    # Exactness across both paths is pinned by the cross-layout tests.
    bucket_prefill: bool = True
    # --- model sharding --------------------------------------------------
    # shard_model=True spreads weights and full-length KV (contiguous rows
    # or page pools) over ``mesh`` — a 1-D ("model",) jax Mesh, defaulting
    # to sharding/utils.serving_mesh() over every local device. Storage
    # shards; compute stays replicated behind an explicit gather boundary,
    # which is what keeps the sharded engine token-for-token identical to
    # the single-device one (docs/sharding.md). Block tables and the
    # BlockAllocator stay host-side/replicated, so incremental page growth
    # and preemption never relayout the sharded pools.
    shard_model: bool = False
    mesh: Any = None                 # jax Mesh; None = serving_mesh()
    # Engine-default decoding policy; per-request SamplingParams override it
    # slot-by-slot through the scheduler. None = SamplingParams.greedy().
    sampling: Optional[SamplingParams] = None
    # Warped-proposal drafting: rows with temperature > 0 SAMPLE their K
    # drafts from the row-warped drafter distribution (one salted
    # counter-based key per slot — sampling.draft_keys) instead of taking
    # the drafter argmax, and verification receives that distribution as
    # the rejection proposal q. Greedy rows stay bitwise on the argmax
    # path. Off by default: the one-hot argmax proposal is the
    # pre-adaptive behavior.
    draft_sampling: bool = False
    # --- swap-to-host preemption -----------------------------------------
    # swap="host": on preemption the victim slot's state — every KV page it
    # exclusively owns (refcount == 1) plus its per-slot rows (recurrent
    # stream state, tokens/logprobs, sampling policy, taps) — is copied to
    # a host-side cache_ops.HostPagePool, and resume becomes a device
    # scatter (swap_in_slot) instead of a recompute-prefill: bitwise the
    # state the victim had at its eviction step boundary. Pages shared
    # with the prefix cache (or another slot) stay resident — the swap
    # handle keeps the slot's reference, pinning them — and are re-mapped
    # on swap-in. Paged-only. host_pool_bytes caps the host snapshot
    # budget (0 = unbounded); when it can't hold a victim, the scheduler
    # falls back to lossless recompute-prefill preemption.
    swap: str = "none"               # none | host
    host_pool_bytes: int = 0         # host snapshot budget; 0 = unbounded

    def __post_init__(self):
        if self.greedy is not None:
            warnings.warn(
                "EngineConfig(greedy=...) is deprecated: decoding policy is "
                "per-request now — pass SamplingParams (e.g. "
                "Request(sampling=SamplingParams(temperature=0.8, seed=1)) "
                "or EngineConfig(sampling=...)) instead",
                DeprecationWarning, stacklevel=2)
            if self.sampling is None:
                object.__setattr__(
                    self, "sampling",
                    SamplingParams.greedy() if self.greedy
                    else SamplingParams(temperature=1.0))
        if self.sampling is None:
            object.__setattr__(self, "sampling", SamplingParams.greedy())
        # keep reads of .greedy meaningful for stragglers (no warning)
        object.__setattr__(self, "greedy", self.sampling.is_greedy)


def make_decode_state(model, tcfg: ModelConfig, dcfg: Optional[DrafterConfig],
                      ecfg: EngineConfig, batch: int, *,
                      cache_dtype=None, taps_dtype=None,
                      last_fill: int = 0, new_count_fill: int = 1,
                      sampling: Optional[dict] = None) -> dict:
    """The ONE definition of the decode-state skeleton (keys + shapes).

    Engine prefill, Engine.blank_state, and the dry-run's serve_step state
    template (launch/steps.py) all build from this, so a new state leaf added
    for speculative_step can't silently go missing at one of the sites.

    ``sampling`` is the per-slot decoding-policy subtree
    (serving/sampling.batch_sampling_state); None fills every slot with the
    engine-default ``ecfg.sampling``."""
    cdt = jnp.dtype(ecfg.cache_dtype) if cache_dtype is None else cache_dtype
    state = {
        "tokens": jnp.zeros((batch, ecfg.max_len), jnp.int32),
        # log p(token) under the RAW target softmax at each committed
        # position (the verification distribution, before any
        # temperature/top-k/top-p warp) — one uniform convention for greedy
        # and sampled rows, harvested alongside "tokens". Prompt positions
        # are never written and read as 0.
        "logprobs": jnp.zeros((batch, ecfg.max_len), jnp.float32),
        "last": jnp.full((batch,), last_fill, jnp.int32),
        "taps_last": jnp.zeros((batch, 3 * tcfg.d_model),
                               taps_dtype if taps_dtype is not None else cdt),
        "tcache": model.make_cache(batch, ecfg.max_len, dtype=cdt),
        "new_count": jnp.full((batch,), new_count_fill, jnp.int32),
        "slot_iters": jnp.zeros((batch,), jnp.int32),
        "iters": jnp.zeros((), jnp.int32),
        "row_iters": jnp.zeros((), jnp.int32),
        "committed": jnp.zeros((), jnp.int32),
        "sampling": (sampling if sampling is not None
                     else batch_sampling_state(ecfg.sampling, batch)),
    }
    if ecfg.drafter_mode != "none":
        state["dcache"] = D.make_cache(dcfg, batch, ecfg.max_len, dtype=cdt)
        # row c: the K drafts proposed from committed position c (the
        # iteration that verified from last == c); -1 where none was
        state["drafts"] = jnp.full((batch, ecfg.max_len, ecfg.K), -1,
                                   jnp.int32)
    return state


@dataclass
class _SwapHandle:
    """One swapped-out request's host-side snapshot (HostPagePool entry).

    ``snap`` is the device_get of ``cache_ops.extract_slot`` trimmed to
    what must actually move: per-slot rows in full, paged-leaf views cut
    down to the spans of the ``host_idx`` pages (zero-size placeholders
    elsewhere — swap-in rebuilds the full-width view around them and its
    scatter mask drops the placeholder spans). ``page_row`` is the slot's
    ordered page list at eviction; pages NOT in ``host_idx`` stayed
    resident on device — the handle kept the slot's allocator reference
    for them, which pins them against prefix-cache LRU eviction until
    swap-in remaps or drop_swap releases them."""
    snap: dict
    page_row: List[int]       # ordered pages at eviction time
    host_idx: List[int]       # row indices whose pages moved to host
    last: int                 # committed step-boundary position
    sampled: bool             # _slot_sampled mirror to restore
    nbytes: int


class Engine:
    """Batched speculative-decoding engine over ``batch`` request slots.

    Args:
      tcfg: target-model config (any family in the model zoo).
      dcfg: drafter config, or None when ``ecfg.drafter_mode == "none"``.
      tparams / dparams: target / drafter parameter pytrees. Under
        ``ecfg.shard_model`` they are re-placed storage-sharded over the
        serving mesh at construction.
      ecfg: static engine configuration (see :class:`EngineConfig`).
      batch: number of decode slots (the fixed batch dimension of the
        decode state; the Scheduler admits requests into free slots).
    """

    def __init__(self, tcfg: ModelConfig, dcfg: Optional[DrafterConfig],
                 tparams: dict, dparams: Optional[dict], ecfg: EngineConfig,
                 batch: int):
        self.tcfg, self.dcfg, self.ecfg = tcfg, dcfg, ecfg
        self.tparams, self.dparams = tparams, dparams
        self.batch = batch
        self.model = get_model(tcfg)
        self.pos_offset = (tcfg.vision_tokens
                           if tcfg.family == "vlm" else 0)
        if ecfg.kv_layout not in ("contiguous", "paged"):
            raise ValueError(f"unknown kv_layout {ecfg.kv_layout!r}")
        if ecfg.kv_growth not in ("incremental", "upfront"):
            raise ValueError(f"unknown kv_growth {ecfg.kv_growth!r}")
        self.paged = ecfg.kv_layout == "paged"
        self.incremental = self.paged and ecfg.kv_growth == "incremental"
        if self.paged:
            if ecfg.max_len % ecfg.page_size:
                raise ValueError(
                    f"max_len {ecfg.max_len} must be a multiple of "
                    f"page_size {ecfg.page_size}")
            self.pages_per_slot = ecfg.max_len // ecfg.page_size
            self.pool_pages = ecfg.pool_pages or batch * self.pages_per_slot
            self.allocator = cache_ops.BlockAllocator(self.pool_pages)
            self._slot_pages: List[List[int]] = [[] for _ in range(batch)]
        if ecfg.prefix_cache and not self.paged:
            raise ValueError(
                "prefix_cache requires kv_layout='paged' (pages are the "
                "sharing unit)")
        self.prefix_cache = (PrefixCache(ecfg.page_size)
                             if self.paged and ecfg.prefix_cache else None)
        if ecfg.swap not in ("none", "host"):
            raise ValueError(f"unknown swap {ecfg.swap!r}")
        if ecfg.swap == "host" and not self.paged:
            raise ValueError(
                "swap='host' requires kv_layout='paged' (pages are the "
                "swap unit)")
        self.swap_enabled = ecfg.swap == "host"
        self.host_pool = (cache_ops.HostPagePool(ecfg.host_pool_bytes)
                          if self.swap_enabled else None)
        # bytes the most recent swap_out_slot / swap_in_slot moved — the
        # scheduler reads this right after the call to charge its clock
        # (same read-after-call idiom as last_hit_tokens)
        self.swap_last_bytes = 0
        self._b1_tpl = None          # cached batch-1 contiguous eval_shape
        self._swap_sizes = None      # cached (row bytes, per-page bytes)
        # the previous serving session's final state — cached page content
        # lives in its pool arrays, so serve_state() resumes from it
        self._serve_state: Optional[dict] = None
        # tokens the most recent prefill_into_slot served from cached pages
        # (0 on a cold admission) — the scheduler reads this right after the
        # call to account per-request hit stats
        self.last_hit_tokens = 0
        # raw-target logprob of the token the most recent fresh (non-resume)
        # prefill_into_slot committed — the scheduler pairs it with the
        # returned first token (same read-after-call idiom as
        # last_hit_tokens); 0.0 after a resume (nothing committed)
        self.last_logprob = 0.0
        # host-side mirror of each slot's policy (sampled vs greedy) — set
        # at admission, cleared on free; lets step() pick the greedy-only
        # trace when nothing in the batch samples (purely a perf choice)
        self._slot_sampled = [False] * batch
        self._slot_axes = None
        self._paged_axes = None
        self._pspec = None
        self._pad_unsafe = None
        self._contig_tpl = None
        self._contig_sh = None
        self._paged_sh = None
        # --- model sharding (storage-sharded, replicated compute) ---------
        self.mesh = None
        if ecfg.shard_model:
            self.mesh = ecfg.mesh if ecfg.mesh is not None else serving_mesh()
            self._repl = NamedSharding(self.mesh, P())
            self._tparam_sh = self._named(
                shard_rules.serve_param_specs(tparams, self.mesh))
            self.tparams = jax.device_put(tparams, self._tparam_sh)
            self._dparam_sh = self._repl
            if dparams is not None:
                self._dparam_sh = self._named(
                    shard_rules.serve_param_specs(dparams, self.mesh))
                self.dparams = jax.device_put(dparams, self._dparam_sh)
        self._build_jits()

    # ------------------------------------------------------------------
    # jit wiring (plain on one device; explicit NamedSharding in/out
    # shardings under shard_model, so every entry point — steps, admission
    # prefills, slot frees, block-table growth — keeps storage sharded at
    # rest and never relies on sharding propagation across host calls)
    # ------------------------------------------------------------------
    def _named(self, specs):
        """PartitionSpec pytree → NamedSharding pytree on the engine mesh."""
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)

    def _rep(self, tree):
        """The exactness boundary: gather storage-sharded leaves so compute
        downstream runs with single-device tensor shapes (bit-identical to
        the unsharded engine). No-op without a mesh."""
        return tree if self.mesh is None else replicate_tree(tree, self.mesh)

    @staticmethod
    def _greedy_twins(fn, **jit_kwargs):
        """{greedy_only: jitted fn} — every step entry point gets a
        greedy-only twin (static greedy_only=True trace: no warp sorts, no
        categorical draws, the pre-SamplingParams per-step cost);
        ``Engine.step`` picks a twin host-side per call. Both twins emit
        identical tokens for greedy rows, so the choice is purely perf."""
        return {g: jax.jit(functools.update_wrapper(
                    functools.partial(fn, greedy_only=g), fn), **jit_kwargs)
                for g in (False, True)}

    def _set_table_row_impl(self, block_table, slot, row):
        """Block-table row ``slot`` := ``row`` (page growth)."""
        return block_table.at[slot].set(row)

    def _drafts_row_impl(self, drafts, slot):
        return jax.lax.dynamic_index_in_dim(drafts, slot, keepdims=False)

    def _build_jits(self):
        if self.mesh is None:
            self._step = self._greedy_twins(self._step_impl)
            self._prefill = jax.jit(self._prefill_impl)
            self._prefill_pad = jax.jit(self._prefill_pad_impl)
            self._chunk = jax.jit(self._chunk_impl)
            self._sched_step = self._greedy_twins(self._sched_step_impl)
            self._paged_step = self._greedy_twins(self._paged_step_impl,
                                                  donate_argnums=2)
            self._admit = jax.jit(self._admit_impl)
            self._paged_admit = jax.jit(self._paged_admit_impl)
            self._free = jax.jit(self._free_impl)
            self._paged_free = jax.jit(self._paged_free_impl)
            # prefix-cache hit path (invoked only with ecfg.prefix_cache):
            # page ids / start positions are traced, so each entry point
            # costs one trace (plus one per pow2 suffix chunk width)
            self._blank_row = jax.jit(self._blank_row_impl)
            self._copy_page = jax.jit(self._copy_page_impl)
            self._hit_seed = jax.jit(self._hit_seed_impl)
            self._hit_chunk = jax.jit(self._hit_chunk_impl)
            # one trace for every (slot, page-count) combination: slot and
            # the full-width block-table row are both traced, so decode-time
            # growth never recompiles (pinned by tests/test_cache_ops.py)
            self._set_table_row = jax.jit(self._set_table_row_impl)
            self._drafts_row = jax.jit(self._drafts_row_impl)
            if self.paged:
                # swap-to-host: one gather trace serves every (slot, row)
                # pair; scatter is the admit trace minus the resume fixup
                self._swap_gather = jax.jit(self._swap_gather_impl)
                self._swap_scatter = jax.jit(self._swap_scatter_impl)
            return
        rp, tp, dp = self._repl, self._tparam_sh, self._dparam_sh
        # contiguous decode-state sharding: full-length k/v leaves sharded
        # over the KV-head axis (head_dim fallback), the rest replicated —
        # the same tree serves every batch size (specs touch trailing dims)
        csh = self.state_shardings
        jj = jax.jit
        self._step = self._greedy_twins(self._step_impl,
                                        in_shardings=(tp, dp, csh),
                                        out_shardings=csh)
        self._prefill = jj(self._prefill_impl,
                           in_shardings=(tp, dp, rp, rp, rp),
                           out_shardings=csh)
        self._prefill_pad = jj(self._prefill_pad_impl,
                               in_shardings=(tp, dp, rp, rp, rp, rp),
                               out_shardings=csh)
        self._chunk = jj(self._chunk_impl,
                         in_shardings=(tp, dp, csh, rp, rp),
                         out_shardings=csh)
        self._sched_step = self._greedy_twins(
            self._sched_step_impl, in_shardings=(tp, dp, csh, rp, rp, rp),
            out_shardings=csh)
        self._admit = jj(self._admit_impl,
                         in_shardings=(csh, csh, rp, rp, rp),
                         out_shardings=csh)
        self._free = jj(self._free_impl, in_shardings=(csh, rp),
                        out_shardings=csh)
        if self.paged:
            # paged state: k/v *pools* shard on the same trailing axes;
            # positions pools, block tables, per-slot rows replicate —
            # admission/free/growth are then sharded-local data movement
            psh = self.paged_state_shardings
            self._paged_step = self._greedy_twins(
                self._paged_step_impl,
                in_shardings=(tp, dp, (), psh, rp, rp, rp),
                out_shardings=((), psh))
            self._paged_admit = jj(self._paged_admit_impl,
                                   in_shardings=(psh, csh, rp, rp, rp, rp,
                                                 rp),
                                   out_shardings=psh)
            self._paged_free = jj(self._paged_free_impl,
                                  in_shardings=(psh, rp), out_shardings=psh)
            # prefix-cache hit path: pool-to-pool data movement stays
            # sharded (blank/copy); the seeded batch-1 view comes out in
            # the contiguous state sharding and chunk prefills cross the
            # usual replication boundary inside _hit_chunk_impl
            self._blank_row = jj(self._blank_row_impl,
                                 in_shardings=(psh, rp), out_shardings=psh)
            self._copy_page = jj(self._copy_page_impl,
                                 in_shardings=(psh, rp, rp),
                                 out_shardings=psh)
            self._hit_seed = jj(self._hit_seed_impl,
                                in_shardings=(psh, rp, rp, rp, rp),
                                out_shardings=csh)
            self._hit_chunk = jj(self._hit_chunk_impl,
                                 in_shardings=(tp, dp, csh, rp, rp),
                                 out_shardings=csh)
            # swap-to-host: the gathered batch-1 snapshot replicates (it is
            # heading to host memory), and swap-in re-scatters a replicated
            # host payload back into the sharded pools
            self._swap_gather = jj(self._swap_gather_impl,
                                   in_shardings=(psh, rp, rp),
                                   out_shardings=rp)
            self._swap_scatter = jj(self._swap_scatter_impl,
                                    in_shardings=(psh, rp, rp, rp, rp),
                                    out_shardings=psh)
        self._set_table_row = jj(self._set_table_row_impl,
                                 in_shardings=(rp, rp, rp), out_shardings=rp)
        self._drafts_row = jj(self._drafts_row_impl, in_shardings=(rp, rp),
                              out_shardings=rp)

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------
    def _prefill_impl(self, tparams, dparams, prompts, extras, samp):
        tparams, dparams = self._rep(tparams), self._rep(dparams)
        B, P = prompts.shape
        state = make_decode_state(self.model, self.tcfg, self.dcfg,
                                  self.ecfg, B, sampling=samp)
        out = self.model.forward(tparams, prompts, mode="prefill",
                                 cache=state["tcache"], collect_taps=True,
                                 head_last_only=True, **extras)
        fused = P + self.pos_offset          # positions 0..fused-1 committed
        # first generated token: argmax for greedy rows; for sampled rows a
        # seeded draw from the warped target distribution, keyed by the
        # position it determines (fold_in(seed, fused) — see sampling.py)
        first = SD.sample_token(step_keys(samp, fused), out.logits[:, -1],
                                samp["temperature"], samp["top_k"],
                                samp["top_p"])

        tokens = state["tokens"]
        tokens = tokens.at[:, self.pos_offset:self.pos_offset + P].set(prompts)
        tokens = tokens.at[:, fused].set(first)

        state.update(
            tokens=tokens,
            logprobs=state["logprobs"].at[:, fused].set(
                _token_logprob(out.logits[:, -1], first)),
            last=jnp.full((B,), fused, jnp.int32),
            taps_last=out.taps[:, -1],
            tcache=out.cache,
        )
        if self.ecfg.drafter_mode != "none":
            dcache = state["dcache"]
            if P > 1:
                pos = (jnp.arange(P - 1, dtype=jnp.int32)[None]
                       + self.pos_offset)
                pos = jnp.broadcast_to(pos, (B, P - 1))
                # taps at fused positions offset..offset+P-2 (text region)
                dcache = D.extend(self.dcfg, self.tcfg, dparams, dcache,
                                  prompts[:, 1:], out.taps[:, -P:-1], pos)
            state["dcache"] = dcache
        # pin the result replicated: the out_shardings reshard is then pure
        # data movement and can't propagate sharding back into the compute
        return self._rep(state)

    def prefill(self, prompts: Array, extras: Optional[dict] = None,
                sampling: Optional[SamplingParams] = None):
        """Whole-batch prefill: build a fresh decode state for ``prompts``
        (B, P), committing one generated token per row.

        Args:
          prompts: (B, P) int32 token batch — equal lengths; per-request
            admission with varied lengths goes through ``prefill_into_slot``.
          extras: optional modality inputs (vision/encoder embeds, leading
            batch axis B) forwarded to the target's prefill.
          sampling: decoding policy applied to every row (default: the
            engine's ``ecfg.sampling``). Per-request policies go through
            the Scheduler (``Request(sampling=...)``).

        Returns:
          A decode-state dict (see ``make_decode_state``) ready for
          ``step``; under shard_model its KV leaves are placed sharded."""
        B = prompts.shape[0]
        samp = batch_sampling_state(sampling or self.ecfg.sampling, B)
        return self._prefill(self.tparams, self.dparams, prompts,
                             extras or {}, samp)

    # ------------------------------------------------------------------
    # bucketed admission prefill (one trace per power-of-two bucket)
    # ------------------------------------------------------------------
    def _prefill_pad_impl(self, tparams, dparams, prompts, true_len, extras,
                          samp):
        """Attention-family bucketed prefill: ``prompts`` (B, Pb) is the
        prompt right-padded to a power-of-two bucket, ``true_len`` the traced
        real length. Causal attention makes right-pads inert for every real
        position; the pads' cache entries are invalidated afterwards (same
        position-based mechanism as speculative rollback), and logits/taps
        are gathered at the true last position instead of -1."""
        tparams, dparams = self._rep(tparams), self._rep(dparams)
        B, Pb = prompts.shape
        state = make_decode_state(self.model, self.tcfg, self.dcfg,
                                  self.ecfg, B, sampling=samp)
        fused = true_len + self.pos_offset       # positions 0..fused-1 real
        hp = jnp.broadcast_to(fused - 1, (B,)).astype(jnp.int32)
        out = self.model.forward(tparams, prompts, mode="prefill",
                                 cache=state["tcache"], collect_taps=True,
                                 head_positions=hp, **extras)
        first = SD.sample_token(step_keys(samp, fused), out.logits[:, 0],
                                samp["temperature"], samp["top_k"],
                                samp["top_p"])
        taps_last = jnp.take_along_axis(out.taps, hp[:, None, None],
                                        axis=1)[:, 0]

        tokens = state["tokens"]
        tokens = tokens.at[:, self.pos_offset:self.pos_offset + Pb].set(
            prompts)
        tokens = tokens.at[jnp.arange(B), fused].set(first)

        cp = jnp.broadcast_to(fused - 1, (B,))
        zero = jnp.zeros((B,), jnp.int32)
        state.update(
            tokens=tokens,
            logprobs=state["logprobs"].at[jnp.arange(B), fused].set(
                _token_logprob(out.logits[:, 0], first)),
            last=jnp.broadcast_to(fused, (B,)).astype(jnp.int32),
            taps_last=taps_last,
            tcache=cache_ops.commit(out.cache, None, cp, zero),
        )
        if self.ecfg.drafter_mode != "none":
            dcache = state["dcache"]
            if Pb > 1:
                pos = (jnp.arange(Pb - 1, dtype=jnp.int32)[None]
                       + self.pos_offset)
                pos = jnp.broadcast_to(pos, (B, Pb - 1))
                dcache = D.extend(self.dcfg, self.tcfg, dparams, dcache,
                                  prompts[:, 1:], out.taps[:, -Pb:-1], pos)
                # pad pairs wrote drafter positions beyond the real prompt
                dcache = cache_ops.commit(dcache, None, cp - 1, zero)
            state["dcache"] = dcache
        return self._rep(state)

    def _chunk_impl(self, tparams, dparams, state, chunk, start):
        """Recurrent-family bucketed prefill step: feed ``chunk`` (B, c) of
        the prompt through a decode-mode forward at positions ``start..``.
        Exact for SSM/RG-LRU state (pads would corrupt the recurrence, so
        chunking replaces padding); each chunk size is a power of two, so a
        length-P prompt costs popcount(P) cached traces."""
        tparams, dparams = self._rep(tparams), self._rep(dparams)
        state = self._rep(state)
        B, c = chunk.shape
        off = self.pos_offset
        positions = jnp.broadcast_to(
            (start + off + jnp.arange(c, dtype=jnp.int32))[None], (B, c))
        out = self.model.forward(tparams, chunk, mode="decode",
                                 positions=positions, cache=state["tcache"],
                                 collect_taps=True, head_last_only=True)
        fused = start + off + c
        samp = state["sampling"]
        first = SD.sample_token(step_keys(samp, fused), out.logits[:, -1],
                                samp["temperature"], samp["top_k"],
                                samp["top_p"])
        tokens = jax.lax.dynamic_update_slice(state["tokens"], chunk,
                                              (0, start + off))
        tokens = tokens.at[jnp.arange(B), fused].set(first)
        new = dict(state)
        new.update(
            tokens=tokens,
            logprobs=state["logprobs"].at[jnp.arange(B), fused].set(
                _token_logprob(out.logits[:, -1], first)),
            last=jnp.broadcast_to(fused, (B,)).astype(jnp.int32),
            taps_last=out.taps[:, -1],
            tcache=out.cache,
        )
        if self.ecfg.drafter_mode != "none":
            # drafter pair at position p pairs (taps[p], token[p+1]): the
            # chunk supplies tokens start..start+c-1, so taps come from the
            # previous chunk's last tap followed by this chunk's first c-1
            taps = jnp.concatenate([state["taps_last"][:, None],
                                    out.taps[:, :-1]], axis=1)
            dpos = jnp.broadcast_to(
                (start - 1 + off + jnp.arange(c, dtype=jnp.int32))[None],
                (B, c))
            new["dcache"] = D.extend(self.dcfg, self.tcfg, dparams,
                                     state["dcache"], chunk, taps, dpos)
        return self._rep(new)

    @staticmethod
    def prefill_buckets(length: int) -> List[int]:
        """MSB-first power-of-two decomposition of a prompt length — the
        chunk sizes of a bucketed recurrent-family prefill. (Attention
        families instead right-pad to the next power of two: one forward.)"""
        return [1 << b for b in range(length.bit_length() - 1, -1, -1)
                if length >> b & 1]

    def _chunk_only(self) -> bool:
        """Bucketing strategy: padding is only sound when every cache
        position is append-only. Recurrent state (ssm/hybrid) would fold the
        pads into the recurrence, and ring (sliding-window) KV wraps on
        write — a pad past the window evicts live prompt entries — so both
        take the MSB-chunking path; pure append-only attention pads."""
        if self._pad_unsafe is None:
            tpl = jax.eval_shape(
                self._prefill_impl, self.tparams, self.dparams,
                jax.ShapeDtypeStruct((1, 4), jnp.int32), {},
                sampling_state_sds(1))
            self._pad_unsafe = (
                self.tcfg.family in ("ssm", "hybrid")
                or cache_ops.has_ring_cache(tpl["tcache"], self.ecfg.max_len))
        return self._pad_unsafe

    def _admission_prefill(self, prompt, extras, samp):
        """Batch-1 prefill for slot admission, bucketed per EngineConfig.
        ``samp`` is the request's device-side sampling row
        (batch_sampling_state at batch 1)."""
        P = int(prompt.shape[1])
        if not self.ecfg.bucket_prefill:
            return self._prefill(self.tparams, self.dparams, prompt, extras,
                                 samp)
        if self._chunk_only():
            sizes = self.prefill_buckets(P)
            state = self._prefill(self.tparams, self.dparams,
                                  prompt[:, :sizes[0]], extras, samp)
            start = sizes[0]
            for c in sizes[1:]:
                state = self._chunk(self.tparams, self.dparams, state,
                                    prompt[:, start:start + c],
                                    jnp.asarray(start, jnp.int32))
                start += c
            return state
        Pb = 1 << max(P - 1, 0).bit_length()     # next power of two >= P
        if self.pos_offset + Pb >= self.ecfg.max_len:
            # bucket would pad past the cache (long recompute-prefill
            # resumes, vlm offsets): take the exact-length trace instead
            return self._prefill(self.tparams, self.dparams, prompt, extras,
                                 samp)
        padded = jnp.pad(prompt, ((0, 0), (0, Pb - P)))
        return self._prefill_pad(self.tparams, self.dparams, padded,
                                 jnp.asarray(P, jnp.int32), extras, samp)

    # ------------------------------------------------------------------
    # one speculative iteration
    # ------------------------------------------------------------------
    def _step_impl(self, tparams, dparams, state, greedy_only=False):
        tparams, dparams = self._rep(tparams), self._rep(dparams)
        out = speculative_step(self.model, self.tcfg, self.dcfg, self.ecfg,
                               tparams, dparams, self._rep(state),
                               greedy_only=greedy_only)
        return self._rep(out)

    # ------------------------------------------------------------------
    # per-slot lifecycle (continuous batching; serving/scheduler.py)
    # ------------------------------------------------------------------
    @property
    def slot_axes(self):
        """Per-leaf batch axis of the decode state, inferred structurally
        (cache_ops.batch_axes) from abstract prefills at batch 1 vs 2.
        Computed once; static thereafter (required: axes feed lax slicing)."""
        if self._slot_axes is None:
            def pf(b):
                return jax.eval_shape(
                    self._prefill_impl, self.tparams, self.dparams,
                    jax.ShapeDtypeStruct((b, 4), jnp.int32), {},
                    sampling_state_sds(b))
            self._slot_axes = cache_ops.batch_axes(pf(1), pf(2))
        return self._slot_axes

    def _abstract_state(self):
        """Cached abstract (jax.eval_shape) contiguous decode state at the
        engine batch — the ONE template pspec / state_shardings /
        blank_state all derive from, so the full prefill is abstract-traced
        once per Engine, not once per consumer."""
        if self._contig_tpl is None:
            self._contig_tpl = jax.eval_shape(
                self._prefill_impl, self.tparams, self.dparams,
                jax.ShapeDtypeStruct((self.batch, 4), jnp.int32), {},
                sampling_state_sds(self.batch))
        return self._contig_tpl

    @property
    def pspec(self):
        """Paged-layout leaf tags (cache_ops.paged_spec) over the decode
        state: which leaves live in the page pool vs per-slot rows."""
        if self._pspec is None:
            self._pspec = cache_ops.paged_spec(self._abstract_state(),
                                               self.ecfg.max_len)
        return self._pspec

    @property
    def in_place_spec(self):
        """Tags of the target cache's leaves that the paged step reads and
        writes in place (its pools); every tag NOT_PAGED where there are
        none. The model's decode reads these through the block table, so
        no per-slot view of them is built. The sharded engine gathers
        every paged leaf, so that its step computes with single-device
        shapes."""
        tspec = self.pspec["tcache"]
        if self.mesh is not None:
            return jax.tree.map(lambda _: cache_ops.NOT_PAGED, tspec)
        return tspec

    @property
    def paged_leaves(self) -> Dict[str, int]:
        """How many paged leaves the decode step reads in place and how
        many it still gathers into the per-slot view (0 and 0 off the paged
        layout)."""
        if not self.paged:
            return {"in_place": 0, "gathered": 0}
        tags = jax.tree.leaves(self.pspec)
        n = sum(t != cache_ops.NOT_PAGED for t in tags)
        k = sum(t != cache_ops.NOT_PAGED
                for t in jax.tree.leaves(self.in_place_spec))
        return {"in_place": k, "gathered": n - k}

    @property
    def paged_axes(self):
        """batch_axes of the *paged* state: pool leaves have no batch axis,
        so write_slot/reset_slot skip them automatically and only touch
        per-slot rows."""
        if self._paged_axes is None:
            def blank(b):
                return jax.eval_shape(lambda: cache_ops.paged_state(
                    make_decode_state(self.model, self.tcfg, self.dcfg,
                                      self.ecfg, b),
                    self.pspec, self.ecfg.page_size, self.pool_pages))
            self._paged_axes = cache_ops.batch_axes(blank(1), blank(2))
        return self._paged_axes

    @property
    def state_shardings(self):
        """NamedSharding pytree of the contiguous decode state (shard_model
        only): attention k/v leaves (full-length rows and ring windows)
        storage-shard over the KV-head axis ("model"), everything else
        replicates (sharding/rules.serve_state_specs). One tree serves
        every batch size — the sharded axes are trailing (KV, hd) dims
        that batch doesn't touch."""
        if self._contig_sh is None:
            self._contig_sh = self._named(shard_rules.serve_state_specs(
                self._abstract_state(), self.mesh))
        return self._contig_sh

    @property
    def paged_state_shardings(self):
        """NamedSharding pytree of the paged decode state (shard_model
        only): k/v page *pools* shard over the same trailing (KV, hd) axes,
        position pools / block tables / per-slot rows replicate — so page
        growth, admission scatters, and preemption frees are sharded-local
        data movement, never a pool relayout."""
        if self._paged_sh is None:
            tpl = jax.eval_shape(lambda: cache_ops.paged_state(
                make_decode_state(self.model, self.tcfg, self.dcfg,
                                  self.ecfg, self.batch),
                self.pspec, self.ecfg.page_size, self.pool_pages))
            tpl["block_table"] = jax.ShapeDtypeStruct(
                (self.batch, self.pages_per_slot), jnp.int32)
            self._paged_sh = self._named(
                shard_rules.serve_state_specs(tpl, self.mesh))
        return self._paged_sh

    def blank_state(self) -> dict:
        """An all-idle batch state: empty caches (positions -1), zero tokens,
        every slot frozen (new_count == max_new_tokens so the budget check
        keeps it inert). Slots come alive via ``prefill_into_slot``, which
        also scatters the request's per-slot sampling-policy row. In the
        paged layout, full-length KV leaves are page pools and the state
        carries a per-slot ``block_table`` (B, max_len/page_size), all -1."""
        sds = self._abstract_state()
        state = make_decode_state(
            self.model, self.tcfg, self.dcfg, self.ecfg, self.batch,
            taps_dtype=sds["taps_last"].dtype,
            new_count_fill=self.ecfg.max_new_tokens,
            sampling=blank_sampling_state(self.batch))
        if self.paged:
            state = cache_ops.paged_state(state, self.pspec,
                                          self.ecfg.page_size,
                                          self.pool_pages)
            state["block_table"] = jnp.full(
                (self.batch, self.pages_per_slot), -1, jnp.int32)
        if self.mesh is not None:
            state = jax.device_put(state, self.paged_state_shardings
                                   if self.paged else self.state_shardings)
        return state

    def serve_state(self) -> dict:
        """Decode state to START a serving session with. Cache-off engines
        always start blank; a prefix-cache engine resumes from the previous
        session's retained state — cached page CONTENT lives in the state's
        pool arrays (the host-side index only maps page ids), so starting
        from a fresh blank pool would orphan every index entry onto zeroed
        pages. The retained state has every slot freed (block-table rows
        -1, counters inert); only held pages carry meaningful bytes. The
        session's first paged step consumes its target pools (they are
        donated), so only the state that session retains at its end can
        start the next one."""
        if self.prefix_cache is None or self._serve_state is None:
            return self.blank_state()
        return self._serve_state

    def retain_state(self, state: dict) -> None:
        """Hand a serving session's final state back for cross-session page
        reuse (no-op without a prefix cache). Scheduler.serve calls this
        after draining; between sessions the engine keeps exactly one state
        alive, so pool memory is not duplicated."""
        if self.prefix_cache is not None:
            self._serve_state = state

    @property
    def commit_stride(self) -> int:
        """Max positions one speculative iteration writes into the cache
        (K drafted + 1 bonus; 1 for vanilla AR): the capacity headroom a
        slot needs beyond its last committed position before it may step."""
        return (self.ecfg.K if self.ecfg.drafter_mode != "none" else 0) + 1

    def pages_for(self, length: int) -> int:
        """Pages covering ``length`` cache positions (capped at max_len)."""
        if not self.paged:
            return 0
        return -(-min(max(length, 1), self.ecfg.max_len)
                 // self.ecfg.page_size)

    def pages_needed(self, prompt_len: int,
                     max_new: Optional[int] = None) -> int:
        """KV pages one request occupies for its whole lifetime: prompt +
        budget + worst-case speculative overshoot, in page units."""
        if not self.paged:
            return 0
        budget = self.ecfg.max_new_tokens if max_new is None else max_new
        return self.pages_for(prompt_len + self.pos_offset + budget
                              + self.ecfg.K + 1)

    def initial_pages(self, prompt_len: int,
                      max_new: Optional[int] = None, *,
                      resume: bool = False) -> int:
        """Pages admission claims up front. Upfront growth reserves the
        whole lifetime (``pages_needed``); incremental growth claims only
        the prompt plus one speculative block — ``ensure_capacity`` grows
        the allocation as the slot's length actually crosses page
        boundaries during decode.

        ``resume`` (incremental only): a no-commit recompute-prefill of a
        preempted SAMPLED stream needs one position LESS than a fresh
        admission of the same length. A fresh prefill of ``prompt_len``
        tokens commits one extra token (last = prompt_len + offset), so the
        next step writes positions up to last + K and the claim must cover
        ``prompt_len + offset + K + 1``. A resume forces the stream's final
        token at position ``prompt_len - 1 + offset`` without committing
        past it, so the next step tops out one position earlier — claiming
        the fresh-size block would over-reserve a page whenever
        ``prompt_len + offset + K`` lands on a page boundary."""
        if not self.paged:
            return 0
        if not self.incremental:
            return self.pages_needed(prompt_len, max_new)
        return self.pages_for(prompt_len + self.pos_offset
                              + self.commit_stride - (1 if resume else 0))

    def can_admit(self, prompt_len: int, max_new: Optional[int] = None,
                  full: bool = False, tokens=None,
                  resume: bool = False) -> bool:
        """Whether the pool can admit one more request of this shape right
        now (always True for the contiguous layout — a free slot is a free
        max_len row). ``full`` gates on the whole-lifetime need even under
        incremental growth — the scheduler uses it when re-admitting a
        preempted request, so a resumed victim cannot be immediately
        re-evicted by the same pressure that evicted it.

        With a prefix cache, cache-only pages count as reclaimable (they
        are evicted LRU on allocation pressure, so a full pool of cold
        cache entries never wedges admission), and passing the prompt
        ``tokens`` gates on the EFFECTIVE post-hit need: pages the prompt
        will map from the cache don't have to come off the free list.

        ``resume`` must mirror the ``prefill_into_slot(resume=...)`` flag of
        the admission being gated, so the gate prices exactly the pages the
        claim will take (see :meth:`initial_pages` — a no-commit resume
        claims one position less)."""
        if not self.paged:
            return True
        need = (self.pages_needed(prompt_len, max_new) if full
                else self.initial_pages(prompt_len, max_new, resume=resume))
        avail = self.allocator.n_free
        if self.prefix_cache is not None:
            pinned = ()
            if tokens is not None and self._hits_ok():
                shared, cow = self.prefix_cache.probe(tokens)
                need -= len(shared)
                # the hit itself pins its shared pages (and CoW source), so
                # they can't double as eviction headroom for the fresh ones
                pinned = shared + ([cow] if cow is not None else [])
            avail += self.prefix_cache.evictable(self.allocator, pinned)
        return need <= avail

    def slot_capacity(self, slot: int) -> int:
        """Cache positions the slot's current page allocation covers."""
        if not self.paged:
            return self.ecfg.max_len
        return len(self._slot_pages[slot]) * self.ecfg.page_size

    def ensure_capacity(self, state: dict, slot: int, length: int):
        """Grow ``slot``'s page allocation to cover ``length`` positions,
        claiming pages from the pool only when the slot's length actually
        crossed a page boundary. Returns ``(state, ok)`` — ``ok`` False
        when the pool is exhausted (the caller preempts or stalls the
        slot; stepping a slot without capacity would silently drop KV
        writes beyond its pages). No-op (always ok) for contiguous
        layouts and upfront growth, where capacity was reserved at
        admission."""
        if not self.incremental:
            return state, True
        need = self.pages_for(length)
        have = len(self._slot_pages[slot])
        if need <= have:
            return state, True
        got = self._alloc_pages(need - have)
        if got is None:
            return state, False
        self._slot_pages[slot].extend(got)
        with span("serve.blank"):
            # blank-on-alloc: a recycled page may carry the previous
            # owner's stale positions, and growth splices it into the table
            # without the full overwrite an admission scatter does — blank
            # BEFORE the table maps it, so it can never read as attendable
            # history
            grow = np.full((self.pages_per_slot,), -1, np.int32)
            grow[:len(got)] = got
            state = self._blank_row(state, jnp.asarray(grow))
            row = np.full((self.pages_per_slot,), -1, np.int32)
            row[:len(self._slot_pages[slot])] = self._slot_pages[slot]
            state = dict(state)
            state["block_table"] = self._set_table_row(
                state["block_table"], jnp.asarray(slot, jnp.int32),
                jnp.asarray(row))
        return state, True

    def slot_drafts(self, state: dict, slot: int) -> Optional[np.ndarray]:
        """Host copy of ``slot``'s draft log, (max_len, K) int32: row c
        holds the K drafts proposed from committed position c, -1 where
        none was. None when the engine runs no drafter. One slot-row
        readback; the scheduler makes it once per finished or evicted
        request, never per iteration."""
        if "drafts" not in state:
            return None
        return np.asarray(self._drafts_row(state["drafts"],
                                           jnp.asarray(slot, jnp.int32)))

    def prefill_into_slot(self, state: dict, prompt, slot: int,
                          extras: Optional[dict] = None,
                          sampling: Optional[SamplingParams] = None,
                          max_new: Optional[int] = None,
                          resume: bool = False):
        """Admit one request into batch row ``slot`` of a live state: prefill
        the prompt as a batch-1 state (bucketed to power-of-two lengths when
        ``bucket_prefill``), then scatter every batched leaf's row into the
        slot (cache_ops.write_slot) — including the request's per-slot
        ``sampling`` policy row. Neighbor slots are untouched — rows are
        independent through attention, caches, and verification, so
        mid-stream admission cannot perturb already-decoding requests.

        In the paged layout the slot additionally claims
        ``initial_pages(len(prompt), max_new)`` pages from the pool (callers
        gate on ``can_admit``) and the prefilled KV is scattered into those
        pages instead of a contiguous row; under incremental growth the
        claim covers only prompt + one speculative block, and the scheduler
        calls ``ensure_capacity`` before each step as the slot grows.

        With ``EngineConfig(prefix_cache=True)`` (dense targets), the
        prompt is first matched against the engine's
        :class:`~repro.serving.prefix_cache.PrefixCache`: cached pages are
        mapped (refcount-shared) into the slot's block-table row, a
        divergent partial page is copied-on-write, and only the uncached
        suffix is prefilled — token-for-token identical to the cold path.
        ``Engine.last_hit_tokens`` reports how many positions the admission
        served from cache (0 when cold).

        ``resume=False`` (fresh admission): the prefill commits one token —
        greedy rows by argmax, sampled rows by a seeded draw from the warped
        target distribution — and returns ``(new_state, first_token,
        last_pos)`` with new_count starting at 1.

        ``resume=True`` (recompute-prefill of a preempted SAMPLED request,
        ``prompt`` = original prompt + tokens generated before eviction):
        the engine prefills ``prompt[:-1]`` like a fresh admission but
        FORCES the committed token to ``prompt[-1]`` — already known, not
        re-sampled — and starts the slot's committed count at 0. The slot
        then holds exactly the state an uninterrupted run has at a step
        boundary (caches forwarded through the second-to-last prefix token,
        the final token committed-but-not-yet-verified), so the next
        speculative step restarts verification at the same committed prefix
        and re-derives the same ``fold_in(seed, position)`` keys — replaying
        the uninterrupted tokens exactly. Returns ``(new_state, None,
        last_pos)``. (Greedy resumes don't need this: their
        prefill-committed argmax token equals the verify path's token by
        construction.)"""
        prompt = jnp.asarray(prompt, jnp.int32)[None]
        res_tok = jnp.asarray(0, jnp.int32)
        if resume:
            prompt, res_tok = prompt[:, :-1], prompt[0, -1]
        sp = sampling or self.ecfg.sampling
        self._slot_sampled[slot] = not sp.is_greedy
        samp = batch_sampling_state(sp, 1)
        res = jnp.asarray(1 if resume else 0, jnp.int32)
        self.last_hit_tokens = 0
        self.last_logprob = 0.0
        if not self.paged:
            src = self._admission_prefill(prompt, extras or {}, samp)
            state = self._admit(state, src, jnp.asarray(slot, jnp.int32),
                                res, res_tok)
        else:
            if self._slot_pages[slot]:
                raise RuntimeError(f"slot {slot} still holds pages; "
                                   "free_slot it before re-admission")
            n = self.initial_pages(int(prompt.shape[1]) + (1 if resume
                                                           else 0), max_new,
                                   resume=resume)
            hit = None
            if self._hits_ok(extras):
                shared, cow = self.prefix_cache.match(np.asarray(prompt[0]))
                if shared or cow is not None:
                    hit = (shared, cow)
            if hit is not None:
                state, src = self._hit_admission(state, prompt, slot, n,
                                                 hit[0], hit[1], samp, res,
                                                 res_tok)
            else:
                pages = self._alloc_pages(n)
                if pages is None:
                    raise RuntimeError(
                        f"page pool exhausted ({n} needed, "
                        f"{self.allocator.n_free} free); gate on can_admit")
                self._slot_pages[slot] = pages
                row = np.full((self.pages_per_slot,), -1, np.int32)
                row[:n] = pages
                src = self._admission_prefill(prompt, extras or {}, samp)
                state = self._paged_admit(state, src,
                                          jnp.asarray(slot, jnp.int32),
                                          jnp.asarray(row), jnp.asarray(row),
                                          res, res_tok)
                if self._hits_ok(extras):
                    self.prefix_cache.insert_stream(np.asarray(prompt[0]),
                                                    pages, self.allocator)
                    self.prefix_cache.note_admission(0, False)
        last = int(src["last"][0])
        if resume:
            self.last_logprob = 0.0
            return state, None, last
        first = int(src["tokens"][0, last])
        self.last_logprob = float(src["logprobs"][0, last])
        return state, first, last

    @staticmethod
    def _resume_fixup(src, resume, res_tok):
        """Turn a batch-1 admission prefill into a step-boundary resume when
        ``resume`` (traced 0/1) is set: the token committed at ``last`` is
        forced to ``res_tok`` (the prefix's final, already-emitted token —
        the prefill's sampled/argmax draw is discarded) and the committed
        count starts at 0, so nothing is harvested twice and the next step
        verifies the prefix's true continuation."""
        src = dict(src)
        last = src["last"][0]
        keep = src["tokens"][0, last]
        src["tokens"] = src["tokens"].at[0, last].set(
            jnp.where(resume > 0, res_tok, keep))
        src["new_count"] = src["new_count"] * (1 - resume)
        return src

    def _admit_impl(self, dst, src, slot, resume, res_tok):
        return cache_ops.write_slot(
            dst, self._resume_fixup(src, resume, res_tok), slot,
            self.slot_axes)

    def _paged_admit_impl(self, dst, src, slot, row, scatter_row, resume,
                          res_tok):
        """``row`` is the slot's full block-table mapping; ``scatter_row``
        selects which of those pages receive the prefilled view (equal on a
        cold admission; a prefix-cache hit masks its shared prefix pages to
        -1 so only freshly owned suffix/CoW pages are written — shared
        pages already hold exactly the bytes the view carries for them)."""
        core = {k: v for k, v in dst.items() if k != "block_table"}
        core = cache_ops.admit_pages(
            core, self._resume_fixup(src, resume, res_tok), slot, row,
            self.paged_axes, self.pspec, scatter_row=scatter_row)
        core["block_table"] = dst["block_table"].at[slot].set(row)
        return core

    # ------------------------------------------------------------------
    # prefix caching (serving/prefix_cache.py; EngineConfig.prefix_cache)
    # ------------------------------------------------------------------
    def _hits_ok(self, extras: Optional[dict] = None) -> bool:
        """Whether prefix-cache sharing applies to this admission. Pages
        hold the full per-position state only for dense attention targets:
        recurrent families (ssm/hybrid) carry per-slot state outside the
        pools, vlm/encdec condition on per-request extras / position
        offsets, and moe couples batch rows — all of those serve unchanged
        with the cache structurally idle (no matches, no inserts)."""
        return (self.prefix_cache is not None
                and self.tcfg.family == "dense"
                and not extras
                and self.pos_offset == 0)

    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """``allocator.alloc`` with prefix-cache pressure relief: on
        exhaustion, evict least-recently-used cache-only pages (pinned
        pages — refcount > 1 — are skipped) and retry once."""
        got = self.allocator.alloc(n)
        if got is None and self.prefix_cache is not None:
            self.prefix_cache.evict(n - self.allocator.n_free,
                                    self.allocator)
            got = self.allocator.alloc(n)
        return got

    def _blank_row_impl(self, state, row):
        core = {k: v for k, v in state.items() if k != "block_table"}
        core = cache_ops.blank_pages(core, row, self.pspec)
        core["block_table"] = state["block_table"]
        return core

    def _copy_page_impl(self, state, src_page, dst_page):
        core = {k: v for k, v in state.items() if k != "block_table"}
        core = cache_ops.copy_page(core, src_page, dst_page, self.pspec)
        core["block_table"] = state["block_table"]
        return core

    def _hit_seed_impl(self, state, row, tokens_row, start, samp):
        """Seed the batch-1 contiguous state of a prefix-cache hit: gather
        the slot's mapped row (shared prefix pages + CoW copy + fresh
        suffix pages) into the per-slot view and blank every view index >=
        ``start`` — fresh pages may carry a previous owner's stale
        positions, and the CoW page's final drafter entry belongs to a
        different lookahead token. Indices below ``start`` are cached
        content, valid by the full-key invariant (prefix_cache.py). The
        suffix chunks (``_hit_chunk`` then ``_chunk``) then recompute
        positions ``start..P-1`` exactly as a cold prefill would."""
        src = make_decode_state(self.model, self.tcfg, self.dcfg, self.ecfg,
                                1, sampling=samp)
        table = row[None]
        idx = jnp.arange(self.ecfg.max_len, dtype=jnp.int32)

        def seed(blank, pooled, tag):
            if tag == cache_ops.NOT_PAGED:
                return blank
            view = cache_ops.gather_pages(pooled, table, tag)
            if tag == cache_ops.PAGED_POS:
                view = jnp.where(idx >= start, -1, view)
            return view

        keys = (("tcache", "dcache") if self.ecfg.drafter_mode != "none"
                else ("tcache",))
        for key in keys:
            src[key] = jax.tree.map(seed, src[key], state[key],
                                    self.pspec[key])
        src["tokens"] = tokens_row
        src["last"] = jnp.full((1,), start, jnp.int32)
        return src

    def _hit_chunk_impl(self, tparams, dparams, state, chunk, start):
        """First suffix chunk of a prefix-cache hit: identical to
        ``_chunk_impl`` except the drafter pair at position ``start - 1``
        is SKIPPED — it pairs the cached tap at start-1 with the chunk's
        first token, and the full-key scheme guarantees the cached page
        already committed exactly that entry (the lookahead token is part
        of the page's identity), while the tap itself was never recomputed
        here. Later chunks have taps_last and take ``_chunk``."""
        tparams, dparams = self._rep(tparams), self._rep(dparams)
        state = self._rep(state)
        B, c = chunk.shape
        off = self.pos_offset
        positions = jnp.broadcast_to(
            (start + off + jnp.arange(c, dtype=jnp.int32))[None], (B, c))
        out = self.model.forward(tparams, chunk, mode="decode",
                                 positions=positions, cache=state["tcache"],
                                 collect_taps=True, head_last_only=True)
        fused = start + off + c
        samp = state["sampling"]
        first = SD.sample_token(step_keys(samp, fused), out.logits[:, -1],
                                samp["temperature"], samp["top_k"],
                                samp["top_p"])
        tokens = jax.lax.dynamic_update_slice(state["tokens"], chunk,
                                              (0, start + off))
        tokens = tokens.at[jnp.arange(B), fused].set(first)
        new = dict(state)
        new.update(
            tokens=tokens,
            logprobs=state["logprobs"].at[jnp.arange(B), fused].set(
                _token_logprob(out.logits[:, -1], first)),
            last=jnp.broadcast_to(fused, (B,)).astype(jnp.int32),
            taps_last=out.taps[:, -1],
            tcache=out.cache,
        )
        if self.ecfg.drafter_mode != "none" and c > 1:
            dpos = jnp.broadcast_to(
                (start + off + jnp.arange(c - 1, dtype=jnp.int32))[None],
                (B, c - 1))
            new["dcache"] = D.extend(self.dcfg, self.tcfg, dparams,
                                     state["dcache"], chunk[:, 1:],
                                     out.taps[:, :-1], dpos)
        return self._rep(new)

    def _hit_admission(self, state, prompt, slot, n, shared, cow, samp,
                       res, res_tok):
        """Admission fast path when ``prompt`` matched cached pages: map
        the shared pages into the slot's block-table row (incref — the
        cache and the slot now co-own them), copy-on-write the divergent
        partial page if any, and prefill only the uncached suffix through
        decode-mode chunks. Reference-order matters: matched pages and the
        CoW source are pinned BEFORE the fresh allocation so the eviction
        that allocation may trigger can never reclaim them."""
        ps = self.ecfg.page_size
        self.allocator.incref(shared)
        if cow is not None:
            self.allocator.incref([cow])
        fresh = self._alloc_pages(n - len(shared))
        if fresh is None:
            self.allocator.free(shared)
            if cow is not None:
                self.allocator.free([cow])
            raise RuntimeError(
                f"page pool exhausted ({n - len(shared)} needed, "
                f"{self.allocator.n_free} free); gate on can_admit")
        start = len(shared) * ps
        if cow is not None:
            # fresh[0] becomes the slot-owned copy; everything in it is
            # valid except the final drafter entry, so the suffix restarts
            # one position early to recompute it
            state = self._copy_page(state, jnp.asarray(cow, jnp.int32),
                                    jnp.asarray(fresh[0], jnp.int32))
            self.allocator.free([cow])          # unpin the source
            start += ps - 1
        row_pages = shared + fresh
        self._slot_pages[slot] = row_pages
        row = np.full((self.pages_per_slot,), -1, np.int32)
        row[:len(row_pages)] = row_pages
        scat = row.copy()
        scat[:len(shared)] = -1     # never write pages other owners hold
        ptoks = np.asarray(prompt[0])
        tokens_row = np.zeros((1, self.ecfg.max_len), np.int32)
        tokens_row[0, :ptoks.size] = ptoks
        src = self._hit_seed(state, jnp.asarray(row), jnp.asarray(tokens_row),
                             jnp.asarray(start, jnp.int32), samp)
        sizes = self.prefill_buckets(int(prompt.shape[1]) - start)
        src = self._hit_chunk(self.tparams, self.dparams, src,
                              prompt[:, start:start + sizes[0]],
                              jnp.asarray(start, jnp.int32))
        pos = start + sizes[0]
        for c in sizes[1:]:
            src = self._chunk(self.tparams, self.dparams, src,
                              prompt[:, pos:pos + c],
                              jnp.asarray(pos, jnp.int32))
            pos += c
        state = self._paged_admit(state, src, jnp.asarray(slot, jnp.int32),
                                  jnp.asarray(row), jnp.asarray(scat), res,
                                  res_tok)
        # insert-on-admit: the verifiable prompt prefix — including a
        # diverged CoW page, whose full key now carries THIS lookahead
        self.prefix_cache.insert_stream(ptoks, row_pages, self.allocator)
        self.last_hit_tokens = start
        self.prefix_cache.note_admission(start, cow is not None)
        return state, src

    def free_slot(self, state: dict, slot: int,
                  final_tokens=None) -> dict:
        """Reset one slot's per-slot rows to blank (positions -1) and
        refreeze it (new_count = max_new_tokens) so it idles until the next
        admission. In the paged layout this also releases the slot's page
        references — a page returns to the pool at refcount zero, while
        pages the prefix cache (or a sharing slot) still holds survive
        intact — and blanks its block-table row. Mandatory for paged
        engines, or the pool leaks; cosmetic for contiguous (admission
        fully overwrites).

        ``final_tokens`` (prefix-cache engines): the request's committed
        stream — prompt + generated tokens, trimmed to what was actually
        emitted. Every full page the stream verifies (its lookahead token
        included) is indexed before the release, so the NEXT request
        sharing the prefix — including this very request resuming after a
        preemption — admits against cached pages."""
        self._slot_sampled[slot] = False
        if self.paged:
            pages = self._slot_pages[slot]
            if final_tokens is not None and pages and self._hits_ok():
                self.prefix_cache.insert_stream(
                    np.asarray(final_tokens, np.int32).reshape(-1), pages,
                    self.allocator)
            self.allocator.free(pages)
            self._slot_pages[slot] = []
            return self._paged_free(state, jnp.asarray(slot, jnp.int32))
        return self._free(state, jnp.asarray(slot, jnp.int32))

    def _free_impl(self, state, slot):
        return cache_ops.reset_slot(
            state, slot, self.slot_axes,
            fills={"new_count": self.ecfg.max_new_tokens})

    def _paged_free_impl(self, state, slot):
        core = {k: v for k, v in state.items() if k != "block_table"}
        # NO page blanking here: the freed pages may still be mapped by the
        # prefix cache or by sharing slots, and their content must survive.
        # The blank-on-recycle invariant moved to the acquisition side —
        # ensure_capacity blanks growth pages, admission scatters fully
        # overwrite claimed pages (cache_ops.blank_pages docstring).
        core = cache_ops.reset_slot(
            core, slot, self.paged_axes,
            fills={"new_count": self.ecfg.max_new_tokens})
        core["block_table"] = state["block_table"].at[slot].set(
            jnp.full((self.pages_per_slot,), -1, jnp.int32))
        return core

    # ------------------------------------------------------------------
    # swap-to-host preemption (EngineConfig.swap="host")
    # ------------------------------------------------------------------
    def _swap_gather_impl(self, state, slot, row):
        """Batch-1 contiguous snapshot of ``slot``: per-slot rows sliced,
        paged leaves gathered through ``row`` — one jit, the device half
        of swap-out (cache_ops.extract_slot)."""
        core = {k: v for k, v in state.items() if k != "block_table"}
        return cache_ops.extract_slot(core, slot, row, self.paged_axes,
                                      self.pspec)

    def _swap_scatter_impl(self, dst, src, slot, row, scatter_row):
        """Swap-in: ``_paged_admit_impl`` minus the resume fixup — the
        snapshot already IS a step-boundary state, so re-admitting it
        verbatim restores the victim bitwise. ``scatter_row`` masks pages
        that never left the device (-1: dropped by scatter_pages)."""
        core = {k: v for k, v in dst.items() if k != "block_table"}
        core = cache_ops.admit_pages(core, src, slot, row, self.paged_axes,
                                     self.pspec, scatter_row=scatter_row)
        core["block_table"] = dst["block_table"].at[slot].set(row)
        return core

    def _b1_template(self):
        """Cached abstract batch-1 contiguous state (the swap snapshot's
        shapes/dtypes; also the skeleton swap-in rebuilds around)."""
        if self._b1_tpl is None:
            self._b1_tpl = jax.eval_shape(
                self._prefill_impl, self.tparams, self.dparams,
                jax.ShapeDtypeStruct((1, 4), jnp.int32), {},
                sampling_state_sds(1))
        return self._b1_tpl

    def _swap_layout(self):
        """Cached ``(row_bytes, page_bytes)``: host bytes of one slot's
        per-slot rows, and of one page's payload summed across every paged
        leaf — ``swap_bytes_estimate`` prices a victim without touching
        the device."""
        if self._swap_sizes is None:
            row_b = page_b = 0
            for t, ax, tag in zip(jax.tree.leaves(self._b1_template()),
                                  jax.tree.leaves(self.paged_axes),
                                  jax.tree.leaves(self.pspec)):
                n = int(np.prod(t.shape, dtype=np.int64)) * t.dtype.itemsize
                if tag != cache_ops.NOT_PAGED:
                    page_b += n // self.pages_per_slot
                elif ax >= 0:
                    row_b += n
            self._swap_sizes = (row_b, page_b)
        return self._swap_sizes

    @staticmethod
    def _host_span(host_idx: List[int], page: int):
        """View indices (along the W axis) of the pages in ``host_idx``."""
        return np.concatenate([np.arange(i * page, (i + 1) * page)
                               for i in host_idx])

    def swap_bytes_estimate(self, slot: int) -> int:
        """Host bytes swapping ``slot`` out would store right now: its
        per-slot rows plus one page payload per page it exclusively owns
        (refcount == 1; shared pages stay resident)."""
        row_b, page_b = self._swap_layout()
        n_host = sum(1 for p in self._slot_pages[slot]
                     if self.allocator.refcount(p) == 1)
        return row_b + page_b * n_host

    def swap_out_slot(self, state: dict, slot: int, rid):
        """Preempt ``slot`` by copying its state to the host pool under key
        ``rid`` instead of discarding it. Returns ``(state, ok)``: on
        ``ok`` the slot is freed (device pages of refcount 1 recycled,
        shared pages left resident under the handle's reference) and
        ``swap_last_bytes`` holds the bytes parked; ``ok`` False means the
        host pool couldn't take the snapshot — NOTHING changed, the caller
        falls back to recompute-prefill preemption.

        Called only at a harvest/sync boundary (where the scheduler
        preempts): there the slot's state is self-consistent — caches
        forwarded through ``last - 1``, the token at ``last`` committed
        but not yet verified — so restoring it bitwise (swap_in_slot)
        continues the run token-for-token, greedy and seeded-sampled rows
        alike. The committed counters are zeroed in the snapshot to match
        the scheduler's resume convention (``_prev_new = 0``)."""
        if not self.swap_enabled:
            return state, False
        pages = self._slot_pages[slot]
        host_idx = [i for i, p in enumerate(pages)
                    if self.allocator.refcount(p) == 1]
        row_b, page_b = self._swap_layout()
        if not self.host_pool.can_store(row_b + page_b * len(host_idx)):
            return state, False
        ps = self.ecfg.page_size
        row = np.full((self.pages_per_slot,), -1, np.int32)
        row[:len(pages)] = pages
        src = jax.device_get(self._swap_gather(
            state, jnp.asarray(slot, jnp.int32), jnp.asarray(row)))
        span = (self._host_span(host_idx, ps) if host_idx else None)
        ph = np.zeros((0,), np.int8)     # structure-keeping placeholder

        def trim(leaf, ax, tag):
            if tag != cache_ops.NOT_PAGED:
                if span is None:
                    return ph
                w_ax = cache_ops.view_width_axis(leaf.ndim, tag)
                return np.ascontiguousarray(np.take(leaf, span, axis=w_ax))
            return np.asarray(leaf) if ax >= 0 else ph

        snap = jax.tree.map(trim, src, self.paged_axes, self.pspec)
        # committed counters restart at 0 on resume (scheduler convention:
        # _prev_new = 0, budget rebased to the remaining tokens) — the
        # budget arithmetic is shift-invariant, so tokens are unchanged
        snap["new_count"] = np.zeros_like(snap["new_count"])
        snap["slot_iters"] = np.zeros_like(snap["slot_iters"])
        nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(snap))
        h = _SwapHandle(snap=snap, page_row=list(pages), host_idx=host_idx,
                        last=int(snap["last"][0]),
                        sampled=self._slot_sampled[slot], nbytes=nbytes)
        if not self.host_pool.put(rid, h, nbytes):
            return state, False
        # release only the exclusive pages; the handle keeps the slot's
        # reference on the shared remainder (pinning it against eviction)
        self.allocator.free([pages[i] for i in host_idx])
        self._slot_pages[slot] = []
        self._slot_sampled[slot] = False
        self.swap_last_bytes = nbytes
        return self._paged_free(state, jnp.asarray(slot, jnp.int32)), True

    def has_swap(self, rid) -> bool:
        """Whether a host snapshot is parked under ``rid``."""
        return self.swap_enabled and rid in self.host_pool

    def can_swap_in(self, rid, prompt_len: Optional[int] = None,
                    max_new: Optional[int] = None,
                    full: bool = False) -> bool:
        """Admission gate for a swapped resume, priced at its DEVICE-page
        need only: the handle's host pages want fresh device pages; its
        resident pages are already on device. ``full`` (the scheduler's
        anti-thrash re-admission gate) additionally covers the remaining
        lifetime growth beyond what the restore maps, mirroring
        ``can_admit(full=True)`` for recompute resumes."""
        h = self.host_pool.get(rid) if self.swap_enabled else None
        if h is None:
            return False
        need = len(h.host_idx)
        if full and prompt_len is not None:
            need += max(0, self.pages_needed(prompt_len, max_new)
                        - len(h.page_row))
        avail = self.allocator.n_free
        if self.prefix_cache is not None:
            avail += self.prefix_cache.evictable(self.allocator, h.page_row)
        return need <= avail

    def swap_in_slot(self, state: dict, slot: int, rid):
        """Resume a swapped-out request into (empty) ``slot``: allocate
        fresh device pages for the host spans, rebuild the full-width
        batch-1 view around the host payload, and scatter it back with the
        still-resident pages masked out of the write. Returns ``(state,
        last)`` — the restored committed position; the slot then holds
        BITWISE the state it had at eviction (device→host→device
        round-trips preserve bytes, and resident pages were never
        touched). Callers gate on ``can_swap_in``."""
        h = self.host_pool.get(rid) if self.swap_enabled else None
        if h is None:
            raise KeyError(f"no swap handle for request {rid!r}")
        if self._slot_pages[slot]:
            raise RuntimeError(f"slot {slot} still holds pages; "
                               "free_slot it before swap-in")
        fresh = self._alloc_pages(len(h.host_idx)) if h.host_idx else []
        if fresh is None:
            raise RuntimeError(
                f"page pool exhausted ({len(h.host_idx)} needed, "
                f"{self.allocator.n_free} free); gate on can_swap_in")
        pages = list(h.page_row)
        scat = np.full((self.pages_per_slot,), -1, np.int32)
        for i, p in zip(h.host_idx, fresh):
            pages[i] = p
            scat[i] = p
        row = np.full((self.pages_per_slot,), -1, np.int32)
        row[:len(pages)] = pages
        src = self._swap_src(h)
        state = self._swap_scatter(state, src, jnp.asarray(slot, jnp.int32),
                                   jnp.asarray(row), jnp.asarray(scat))
        self._slot_pages[slot] = pages
        self._slot_sampled[slot] = h.sampled
        self.host_pool.pop(rid)
        self.swap_last_bytes = h.nbytes
        return state, h.last

    def _swap_src(self, h: _SwapHandle) -> dict:
        """Full-width batch-1 state around the handle's payload: per-slot
        rows verbatim, paged views zero-filled except the host spans
        (swap-in's scatter mask drops everything else, so the fill value
        is never read), leaves write_slot ignores zero-filled for shape."""
        ps = self.ecfg.page_size
        span = (self._host_span(h.host_idx, ps) if h.host_idx else None)

        def build(t, s, ax, tag):
            if tag != cache_ops.NOT_PAGED:
                full = np.zeros(t.shape, t.dtype)
                if span is not None:
                    sl = [slice(None)] * len(t.shape)
                    sl[cache_ops.view_width_axis(len(t.shape), tag)] = span
                    full[tuple(sl)] = s
                return full
            if ax < 0:
                return np.zeros(t.shape, t.dtype)
            return s

        return jax.tree.map(build, self._b1_template(), h.snap,
                            self.paged_axes, self.pspec)

    def drop_swap(self, rid) -> bool:
        """Release ``rid``'s host snapshot without resuming it: frees the
        host-pool bytes immediately and drops the handle's reference on
        its resident pages (abort of a swapped request, or the scheduler
        falling a swapped resume back to recompute-prefill). False when
        nothing was parked."""
        if not self.has_swap(rid):
            return False
        h = self.host_pool.pop(rid)
        on_host = set(h.host_idx)
        resident = [p for i, p in enumerate(h.page_row) if i not in on_host]
        if resident:
            self.allocator.free(resident)
        return True

    def reset_stats(self) -> None:
        """Restart the allocator's and host pool's ``peak_used`` high-water
        marks at current usage — multi-phase benchmarks (tables 13/19)
        call this between warm-up and measured passes so each phase
        reports its own honest peak."""
        if self.paged:
            self.allocator.reset_stats()
        if self.host_pool is not None:
            self.host_pool.reset_stats()

    def _mixed_policy(self) -> bool:
        """Whether the next step needs the sampled verification lane: any
        admitted slot carries a sampled policy, or the engine default is
        sampled (whole-batch prefill states fill every row with it). False
        selects the greedy-only trace — same tokens, pre-redesign cost."""
        return any(self._slot_sampled) or not self.ecfg.sampling.is_greedy

    def step(self, state: dict, active: Optional[Array] = None,
             max_new: Optional[Array] = None,
             k_row: Optional[Array] = None) -> dict:
        """One jitted speculative iteration. Without arguments this is the
        legacy whole-batch step; the scheduler passes ``active`` (B,) bool and
        per-slot ``max_new`` (B,) int32. The paged layout always routes
        through ``_paged_step_impl``, which takes the target's pools as a
        donated argument of their own: the state passed in gives up those
        buffers to the state returned. Host-side, the engine picks
        the mixed-policy or greedy-only trace of the step (``_mixed_policy``;
        output-identical, the greedy twin just skips the sampled lane's
        warps and draws).

        ``k_row`` (B,) int32 is the adaptive-speculation max-K mask: each
        row's effective draft length this iteration, in ``[0, K]``. It is a
        TRACED argument of the same jitted step — varying it never
        recompiles — and ``None`` (= full K everywhere) is bitwise
        identical to the pre-adaptive step."""
        g = not self._mixed_policy()              # twin key: greedy_only
        B = state["tokens"].shape[0]
        if self.paged:
            if "block_table" not in state:
                raise ValueError(
                    "paged Engine.step needs a paged state (blank_state + "
                    "prefill_into_slot); whole-batch prefill states are "
                    "contiguous-only — use a kv_layout='contiguous' engine "
                    "for whole-batch loops like serve_round_based")
            if active is None:
                active = jnp.ones((B,), bool)
            if max_new is None:
                max_new = jnp.full((B,), self.ecfg.max_new_tokens, jnp.int32)
            if k_row is None:
                k_row = jnp.full((B,), self.ecfg.K, jnp.int32)
            pools, rest = self.split_pools(state)
            pools, rest = self._paged_step[g](
                self.tparams, self.dparams, pools, rest, jnp.asarray(active),
                jnp.asarray(max_new, jnp.int32),
                jnp.asarray(k_row, jnp.int32))
            rest["tcache"] = cache_ops.put_pools(rest["tcache"], pools)
            return rest
        if active is None and max_new is None and k_row is None:
            return self._step[g](self.tparams, self.dparams, state)
        if active is None:
            active = jnp.ones((B,), bool)
        if max_new is None:
            max_new = jnp.full((B,), self.ecfg.max_new_tokens, jnp.int32)
        if k_row is None:
            k_row = jnp.full((B,), self.ecfg.K, jnp.int32)
        return self._sched_step[g](self.tparams, self.dparams, state,
                                   jnp.asarray(active),
                                   jnp.asarray(max_new, jnp.int32),
                                   jnp.asarray(k_row, jnp.int32))

    def _sched_step_impl(self, tparams, dparams, state, active, max_new,
                         k_row, greedy_only=False):
        tparams, dparams = self._rep(tparams), self._rep(dparams)
        out = speculative_step(self.model, self.tcfg, self.dcfg, self.ecfg,
                               tparams, dparams, self._rep(state),
                               active_mask=active, max_new=max_new,
                               k_row=k_row, greedy_only=greedy_only)
        return self._rep(out)

    def split_pools(self, state: dict):
        """``(pools, rest)`` of a paged state: the target cache's leaves the
        step updates in place (``in_place_spec``), and the state without
        them (``cache_ops.take_pools``)."""
        pools, tcache = cache_ops.take_pools(state["tcache"],
                                             self.in_place_spec)
        return pools, {**state, "tcache": tcache}

    def _paged_step_impl(self, tparams, dparams, pools, state, active,
                         max_new, k_row, greedy_only=False):
        """Paged twin of _sched_step_impl. ``pools`` are the target cache's
        pools (``split_pools``), donated: the model's decode reads each
        layer's pages through the block table and writes only the new rows
        into them, and commit writes its rejected rows empty, all in the
        caller's buffers (``L.paged_view`` / ``L.paged_update``,
        ``cache_ops.commit``). Every other paged leaf (a drafter's cache)
        is reassembled into the contiguous per-slot view the step consumes
        (cache_ops.gather) and scattered back through the block table
        after it. Both layouts give the same tokens — the cross-layout
        equivalence tests pin this.

        Under shard_model no pool is read in place: the gathered view (and
        the weights) cross the replication boundary before the step — the
        all-gather of each slot's pages — and the stepped view is pinned
        replicated again before ``scatter_state`` writes it back into the
        sharded pools, so the speculative iteration itself computes with
        single-device shapes (the losslessness invariant) while pools stay
        sharded at rest across the host round-trip."""
        tparams, dparams = self._rep(tparams), self._rep(dparams)
        table = state["block_table"]
        core = {k: v for k, v in state.items() if k != "block_table"}
        core["tcache"] = cache_ops.put_pools(core["tcache"], pools)
        ispec = self.in_place_spec
        gspec = {**self.pspec, "tcache": jax.tree.map(
            lambda t, i: cache_ops.NOT_PAGED if i else t,
            self.pspec["tcache"], ispec)}
        with jax.named_scope("gather"):
            view = self._rep(cache_ops.gather_state(core, table, gspec))
            view["tcache"] = cache_ops.attach_table(view["tcache"], ispec,
                                                    table)
        view = speculative_step(self.model, self.tcfg, self.dcfg, self.ecfg,
                                tparams, dparams, view,
                                active_mask=active, max_new=max_new,
                                k_row=k_row, greedy_only=greedy_only)
        with jax.named_scope("scatter"):
            view = self._rep(view)
            view["tcache"] = cache_ops.detach_table(view["tcache"])
            core = cache_ops.scatter_state(core, view, table, gspec)
        pools, core["tcache"] = cache_ops.take_pools(core["tcache"], ispec)
        core["block_table"] = table
        return pools, core

    # ------------------------------------------------------------------
    # loops & metrics
    # ------------------------------------------------------------------
    def run(self, prompts: Array, extras: Optional[dict] = None,
            max_iters: int = 10_000) -> Dict[str, Any]:
        if self.paged:
            raise ValueError(
                "Engine.run is the whole-batch contiguous loop; drive a "
                "paged engine through serving.Scheduler (per-slot admission "
                "is what allocates pages)")
        t0 = time.perf_counter()
        state = self.prefill(prompts, extras)
        jax.block_until_ready(state["tokens"])
        t_prefill = time.perf_counter() - t0

        iters = 0
        g = self.ecfg.sampling.is_greedy        # whole-batch default policy
        t0 = time.perf_counter()
        while iters < max_iters:
            state = self._step[g](self.tparams, self.dparams, state)
            iters += 1
            if iters % 8 == 0 or iters < 2:
                if bool(np.all(np.asarray(state["new_count"])
                               >= self.ecfg.max_new_tokens)):
                    break
        jax.block_until_ready(state["tokens"])
        t_decode = time.perf_counter() - t0

        new_tok = int(np.sum(np.asarray(state["new_count"])))
        it = max(int(state["iters"]), 1)
        row_iters = max(int(state["row_iters"]), 1)
        return {
            "state": state,
            "tokens": np.asarray(state["tokens"]),
            "new_tokens": new_tok,
            "iterations": it,
            "acceptance_length": int(state["committed"]) / row_iters,
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "otps": new_tok / max(t_decode, 1e-9),
        }


def _token_logprob(logits, tok):
    """log p(tok) under the raw softmax of ``logits`` at each position.

    This is the engine's per-token logprob convention (see
    make_decode_state): the RAW target distribution — what verification
    scores against — not the warped sampling distribution, so greedy and
    sampled rows report comparable values and the number is independent of
    the request's temperature/top-k/top-p knobs. Broadcasts over leading
    axes: (B, V) + (B,) -> (B,), (B, K+1, V) + (B, K+1) -> (B, K+1)."""
    lp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(
        lp, tok[..., None].astype(jnp.int32), axis=-1)[..., 0]


def speculative_step(model, tcfg: ModelConfig, dcfg: Optional[DrafterConfig],
                 ecfg: EngineConfig, tparams, dparams, state,
                 active_mask: Optional[Array] = None,
                 max_new: Optional[Array] = None,
                 k_row: Optional[Array] = None,
                 greedy_only: bool = False):
    """One speculative iteration: draft K → verify K+1 → accept → commit.

    Pure function of (params, state) — shared by the Engine and by the
    dry-run's ``serve_step`` lowering (launch/steps.py).

    ``active_mask`` (B,) bool and ``max_new`` (B,) int32 are the continuous-
    batching hooks: the scheduler masks out free/finished slots and supplies
    per-request token budgets. Both default to the legacy whole-batch
    behavior (all slots live, shared ``ecfg.max_new_tokens`` budget), so
    existing callers are unchanged. A masked row commits nothing and its
    last/taps/counters are frozen; its cache rows receive only garbage that
    the next ``Engine.prefill_into_slot`` fully overwrites.

    Verification policy is per row (``state["sampling"]``, see
    serving/sampling.py): ``temperature == 0`` rows take the exact greedy
    argmax path on the raw target logits; the rest run seeded rejection
    sampling against the row-warped drafter/target distributions, with the
    row's key re-derived every step as ``fold_in(base_key, c + 1)`` — the
    position of the first token the step determines — so a row's stream
    depends only on its own ``(seed, committed prefix)``, never on batch
    composition, slot index, or an engine-global RNG.

    With ``ecfg.draft_sampling`` the sampled rows' K drafts are themselves
    DRAWN from the row-warped drafter distribution (keys: a DRAFT_SALT-
    separated fold_in stream at the same position counter — sampling.py)
    and the rejection proposal q is that distribution instead of the argmax
    one-hot; greedy rows keep the argmax drafts bitwise.

    ``k_row`` (B,) int32 caps each row's effective draft length this
    iteration (adaptive K, ``None`` = full K): a max-K mask inside
    verification — slots past k_row are force-rejected losslessly — so the
    scheduler's controller varies speculation depth per row with zero
    retraces. The drafter still emits K slots; the cap costs nothing and
    changes nothing when ``k_row == K``.

    ``greedy_only`` (STATIC) traces the verification without the sampled
    lane at all — no warping, no categorical draws — restoring the
    pre-SamplingParams per-step cost. The Engine selects this trace
    host-side whenever no admitted request is sampled; it is output-
    identical to the mixed trace for all-greedy rows (the mixed trace's
    greedy lane is the same argmax on the same raw logits)."""
    B = state["tokens"].shape[0]
    K = ecfg.K if ecfg.drafter_mode != "none" else 0
    c = state["last"]
    tok_next = jnp.take_along_axis(state["tokens"], c[:, None], axis=1)[:, 0]
    samp = state["sampling"]

    # warped-proposal draft policy: only the mixed trace draws (the greedy
    # twin is selected precisely when no admitted row samples)
    policy = None
    if ecfg.draft_sampling and not greedy_only and K > 0:
        policy = (draft_keys(samp, c + 1, K), samp["temperature"],
                  samp["top_k"], samp["top_p"])

    with jax.named_scope("draft"):
        if ecfg.drafter_mode == "parallel":
            drafts, dlogits, dcache = D.draft_parallel(
                dcfg, tcfg, dparams, state["dcache"], tok_next,
                state["taps_last"], c - 1, K, policy=policy)
        elif ecfg.drafter_mode == "ar":
            drafts, dlogits, dcache = D.draft_ar(
                dcfg, tcfg, dparams, state["dcache"], tok_next,
                state["taps_last"], c - 1, K, policy=policy)
        else:
            drafts = jnp.zeros((B, 0), jnp.int32)
            dlogits, dcache = None, None

    # target verify over [t_last, d_1..d_K] at positions c..c+K
    with jax.named_scope("verify"):
        vt = jnp.concatenate([tok_next[:, None], drafts], axis=1)
        positions = c[:, None] + jnp.arange(K + 1, dtype=jnp.int32)[None]
        tout = model.forward(tparams, vt, mode="decode",
                             positions=positions, cache=state["tcache"],
                             collect_taps=ecfg.drafter_mode != "none")

    with jax.named_scope("accept"):
        if K == 0:
            accept_len = jnp.zeros((B,), jnp.int32)
            if greedy_only:
                t_star = jnp.argmax(tout.logits, axis=-1).astype(jnp.int32)
            else:
                t_star = SD.sample_token(
                    step_keys(samp, c + 1), tout.logits[:, 0],
                    samp["temperature"], samp["top_k"],
                    samp["top_p"])[:, None]
        elif greedy_only:
            accept_len, t_star = SD.greedy_verify(drafts, tout.logits)
            if k_row is not None:
                # clip the matched prefix at the row's draft budget — the
                # correction token t_star[accept_len] is the target argmax
                # at that position, so the stream content is unchanged
                accept_len = jnp.minimum(accept_len, k_row)
        else:
            if policy is not None:
                # sampled rows drew their drafts from the row-warped
                # drafter distribution — the proposal q MUST be that same
                # distribution for rejection sampling to stay lossless.
                # Greedy rows keep the one-hot of their argmax drafts
                # (their sampled-lane output is discarded by
                # mixed_verify's where-select anyway).
                q = jnp.where((samp["temperature"] > 0)[:, None, None],
                              SD.warp_probs(dlogits, samp["temperature"],
                                            samp["top_k"], samp["top_p"]),
                              jax.nn.one_hot(drafts, tout.logits.shape[-1],
                                             dtype=tout.logits.dtype))
            else:
                # drafts are the drafter's argmax — a DETERMINISTIC
                # proposal, so the distribution they were drawn from is a
                # one-hot, and lossless rejection reduces to
                # accept-with-p(d) / residual p-masked-at-d (passing the
                # drafter softmax here would over-accept the drafter's
                # argmax and bias the committed distribution)
                q = jax.nn.one_hot(drafts, tout.logits.shape[-1],
                                   dtype=tout.logits.dtype)
            accept_len, t_star = SD.mixed_verify(
                step_keys(samp, c + 1), drafts, q, tout.logits,
                samp["temperature"], samp["top_k"], samp["top_p"], k_row)

        budget = jnp.asarray(ecfg.max_new_tokens, jnp.int32) \
            if max_new is None else max_new
        active = state["new_count"] < budget
        if active_mask is not None:
            active &= active_mask
        accept_len = jnp.where(active, accept_len, 0)

    with jax.named_scope("commit"):
        # commit target cache (invalidate stale attention slots / select
        # recurrent snapshots at the last accepted token)
        tcache = cache_ops.commit(tout.cache, tout.aux.get("snapshots"),
                                  c + accept_len, accept_len, block=K + 1)

        # append committed tokens t_star[0..accept_len]
        idx = c[:, None] + 1 + jnp.arange(K + 1, dtype=jnp.int32)[None]
        keep = jnp.arange(K + 1)[None] <= accept_len[:, None]
        keep &= active[:, None]
        safe_idx = jnp.where(keep, idx, state["tokens"].shape[1])
        tokens = jax.vmap(lambda t, i, v: t.at[i].set(v, mode="drop"))(
            state["tokens"], safe_idx, t_star)
        # committed-token logprobs ride the same scatter: tout.logits[:, j]
        # is the raw target distribution at position c+j, which determined
        # the token committed at c+1+j — exactly the pairing
        # _token_logprob scores
        logprobs = jax.vmap(lambda t, i, v: t.at[i].set(v, mode="drop"))(
            state["logprobs"], safe_idx, _token_logprob(tout.logits, t_star))

        new_last = jnp.where(active, c + accept_len + 1, c)
        taps_last = state["taps_last"]
        if ecfg.drafter_mode != "none":
            taps_new = jnp.take_along_axis(
                tout.taps, accept_len[:, None, None], axis=1)[:, 0]
            taps_last = jnp.where(active[:, None], taps_new, taps_last)
            # extend drafter cache across the verified block (stale tail is
            # auto-invalidated by the next positional write)
            dcache = D.extend(dcfg, tcfg, dparams, dcache, t_star, tout.taps,
                              positions)
            # the drafts land in row c, the position they were proposed
            # from, the way tokens are written (inactive rows drop)
            row = jnp.where(active, c, state["drafts"].shape[1])
            drafts_log = jax.vmap(
                lambda t, i, v: t.at[i].set(v, mode="drop"))(
                state["drafts"], row, drafts)

    ncommit = jnp.where(active, accept_len + 1, 0)
    new_state = dict(
        tokens=tokens,
        logprobs=logprobs,
        last=new_last,
        taps_last=taps_last,
        tcache=tcache,
        new_count=state["new_count"] + ncommit,
        slot_iters=state["slot_iters"] + active.astype(jnp.int32),
        iters=state["iters"] + jnp.any(active).astype(jnp.int32),
        row_iters=state["row_iters"] + jnp.sum(active.astype(jnp.int32)),
        committed=state["committed"] + jnp.sum(ncommit),
        sampling=samp,
    )
    if ecfg.drafter_mode != "none":
        new_state["dcache"] = dcache
        new_state["drafts"] = drafts_log
    return new_state
