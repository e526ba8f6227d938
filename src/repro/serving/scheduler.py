"""Event-driven continuous-batching scheduler — the framework's
request-lifecycle layer over serving/engine.py (what vLLM's scheduler is to
its model runner, and what the paper's deployed-serving numbers §5.4
implicitly rely on).

Request lifecycle::

    QUEUED ──arrive──► (eligible) ──admit──► PREFILLING ──► DECODING ──┐
      ▲                                          ▲                    │
      │                                          │            EOS / budget
      └────────── preempted (pages freed, ───────┘                    │
                  tokens kept host-side)                          FINISHED

    (any state before FINISHED) ──abort──► ABORTED   [streaming driver:
    pages freed immediately, partial output retained host-side]

The loop core (admit → grow → dispatch → harvest) is a set of Scheduler
methods shared by TWO drivers: the deterministic virtual-clock ``serve()``
below, and the wall-clock ``serving/streaming.AsyncEngine`` that streams
``(token, logprob)`` pairs as syncs commit. Every losslessness/churn
property pinned against ``serve()`` therefore exercises the streaming
path's scheduling logic too — the drivers differ only in who advances the
clock and who consumes the emit buffer.

The engine's decode state is a fixed-shape batch of B *slots*; every
speculative iteration steps all B rows under a per-slot active mask. When a
request finishes (per-request ``max_new_tokens`` budget or EOS), its slot is
freed *immediately* — mid-stream — and the next eligible request is prefilled
straight into the live batch (``Engine.prefill_into_slot``), not held until
the whole batch drains.

Arrival times and the virtual clock
-----------------------------------
Requests carry an ``arrival_time`` (virtual time units). The scheduler runs a
deterministic, step-cost-driven **virtual clock**: every dispatched
speculative iteration advances it by ``iter_cost``, every admission prefill
by ``prefill_cost``, and when nothing is live the clock jumps to the next
arrival. No request is admitted before its arrival; among arrived requests
admission is FIFO by ``(arrival_time, submission order)`` with head-of-line
blocking (when the head doesn't fit the page pool the scheduler waits for
frees — or preempts — rather than admitting around it). Because the clock is
derived from step counts, not wall time, async traces replay bit-identically
on CPU test runs; wall-clock metrics are kept alongside for throughput.

Preemption (paged layout)
-------------------------
Under incremental page growth (``EngineConfig(kv_growth="incremental")``) a
slot claims pages only as its length crosses page boundaries, so the pool can
genuinely run out mid-decode. When growth fails — or when the queue head
would starve behind lower-priority runners — the lowest-priority running slot
(latest ``(arrival_time, submission)``) is evicted: its pages return to the
pool and its prompt + generated tokens are retained host-side. It is later
re-admitted by **recompute-prefill** (prompt + generated-so-far becomes the
new prefill), token-for-token losslessly for EVERY decoding policy: greedy
speculative output is a pure function of the prefix, and a seeded sampled
request's continuation is a pure function of ``(seed, prefix)`` — its
per-step keys are ``fold_in(seed, position)`` counters, re-derived over the
recomputed prefix (the resume prefill rebuilds the eviction's exact
step-boundary state and commits nothing new; serving/sampling.py).
tests/test_async_serving.py pins both, per family. Re-admission of a
preempted request gates on its *full* remaining need so the same pressure
cannot immediately re-evict it. With ``EngineConfig(swap="host")`` an
eviction instead parks the victim's pages + per-slot rows in a host-side
pool and the resume is a bitwise device scatter (no prefill re-paid); the
scheduler falls back to recompute-prefill per eviction whenever the host
pool is full or the bytes-moved cost model says recompute is cheaper —
see the ``swap`` knobs below, tests/test_swap.py, and docs/serving.md.

Row independence is the correctness backbone: attention, cache updates, and
verification are all per-row, so admitting into slot *i* cannot change what
slot *j* emits (tests/test_scheduler.py asserts this token-for-token; note
MoE targets with capacity-based routing couple rows and are excluded from
that guarantee).

Termination is host-driven: after each iteration the scheduler reads back
the small per-slot counters plus newly committed tokens, detects per-request
EOS (output trimmed at the first EOS, vLLM semantics) and budget exhaustion,
and retires slots. Speculative commits can overshoot a budget by up to K;
overshoot tokens are trimmed from the emitted output.

The scheduler is device-layout agnostic: it only ever calls the Engine's
jitted entry points and reads back small replicated counters, so a
model-sharded engine (``EngineConfig(shard_model=True)`` — weights and KV
page pools storage-sharded over a device mesh, docs/sharding.md) slots in
with zero changes here and identical token streams (pinned by the sharded
cases in tests/test_serving.py and tests/test_async_serving.py).

Quickstart::

    eng = Engine(tcfg, dcfg, tparams, dparams, EngineConfig(...), batch=4)
    sched = Scheduler(eng, eos_id=None)
    report = sched.serve([Request(p, arrival_time=t) for p, t in work])
    report["otps"], report["p99_latency_vt"], report["results"][0]["tokens"]
"""
from __future__ import annotations

import bisect
import itertools
import time
import zlib
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import make_extras
from repro.serving.engine import Engine
from repro.serving.sampling import SamplingParams
from repro.serving.speculation import SpeculationConfig, SpeculationController
from repro.tracing import GcSpans, span

QUEUED = "queued"
PREFILLING = "prefilling"
DECODING = "decoding"
FINISHED = "finished"
ABORTED = "aborted"

_rid_counter = itertools.count()


@dataclass(eq=False)          # identity semantics: requests hold numpy
class Request:                # arrays, and membership tests (abort from
                              # the wait queue) must mean THIS request
    """One generation request. ``prompt`` is a 1-D int32 token array; the
    prefill commits the first generated token, which counts toward
    ``max_new_tokens`` (None = the engine's default budget).

    ``sampling`` is the request's decoding policy (temperature / top-k /
    top-p / seed / stop tokens — serving/sampling.SamplingParams); None
    falls back to the engine default (``EngineConfig.sampling``, greedy
    unless configured otherwise). A batch may freely mix greedy and sampled
    requests: policy is a per-slot row of the device state, not an engine
    mode. Budget precedence: ``max_new_tokens`` here, else
    ``sampling.max_new_tokens``, else the engine default.

    ``arrival_time`` is in virtual time units — the scheduler will not admit
    the request before its arrival. ``extras`` carries per-request modality
    inputs (vision embeds / encoder embeds, leading batch axis 1, as built
    by ``models.make_extras(cfg, 1, "prefill", key)``); for vlm/encdec
    targets without explicit extras a deterministic stub (keyed by the
    prompt bytes) is synthesized at admission."""
    prompt: Any
    max_new_tokens: Optional[int] = None
    arrival_time: float = 0.0
    extras: Optional[dict] = None
    sampling: Optional[SamplingParams] = None
    rid: int = field(default_factory=lambda: next(_rid_counter))
    # lifecycle (managed by the scheduler)
    status: str = QUEUED
    slot: Optional[int] = None
    out_tokens: List[int] = field(default_factory=list)
    # raw-target logprob of each out_tokens entry (engine._token_logprob
    # convention), maintained in lockstep with out_tokens
    out_logprobs: List[float] = field(default_factory=list)
    # metrics
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_finish: float = 0.0
    vt_admit: Optional[float] = None   # virtual clock at first admission
    vt_finish: float = 0.0
    n_preempt: int = 0
    n_swap: int = 0                # preemptions that swapped to host (the
    #                                rest resumed by recompute-prefill)
    iters: int = 0                 # decode iterations this request was live
    cached_tokens: int = 0         # prompt positions served from the prefix
    #                                cache across all admissions (0 = cold)
    # the engine's draft log of this request (Engine.slot_drafts; row c:
    # the K drafts proposed from stream position c, -1 where none), copied
    # when it finishes, is evicted or aborted; None without a drafter
    drafts: Optional[np.ndarray] = None
    # internal bookkeeping
    _prev_new: int = 0             # device-side new_count at last sync
    _prev_last: int = 0            # device-side last position at last sync
    _iters_base: int = 0           # iters accumulated before the last resume
    _committed: int = 0            # tokens committed across all admissions
    _prefills: int = 0             # prefill-committed tokens (1 + resumes)
    _seq: int = 0                  # submission index (FIFO tie-break)
    _scanned: int = 0              # out_tokens prefix already stop-scanned
    _emitted: int = 0              # out_tokens prefix already streamed out
    _stop_set: Optional[frozenset] = None   # stop ids, frozen at submission

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        if not (self.arrival_time >= 0.0 and np.isfinite(self.arrival_time)):
            raise ValueError(f"bad arrival_time {self.arrival_time!r}")

    @property
    def acceptance_length(self) -> float:
        """Mean tokens committed per decode iteration (prefill-committed
        tokens excluded, one per admission) — the paper's AL, per request."""
        return (self._committed - self._prefills) / max(self.iters, 1)


class Scheduler:
    """Event-driven continuous-batching loop over an Engine's B slots.

    ``eos_id`` — token id that terminates a request (output trimmed at the
    first occurrence, which the losslessness tests rely on being identical
    across drafter modes). ``free_on_finish`` — blank freed slots' cache rows
    (optional; admission fully overwrites a slot either way).

    ``sync_every`` — speculative iterations dispatched between host syncs.
    1 gives the most responsive admission/EOS handling; higher values let jax
    pipeline dispatch (the whole-batch Engine.run polls every 8) at the cost
    of slots idling up to sync_every-1 iterations after finishing, and of
    page growth reserving capacity for the whole block up front. Outputs
    are identical either way: per-slot budgets freeze rows ON DEVICE, and
    EOS/budget trimming is positional, not timing-dependent.

    ``iter_cost`` / ``prefill_cost`` — virtual-clock cost of one speculative
    iteration / one admission prefill. The defaults (1.0 each) make the clock
    an iteration counter; scale them to calibrated step times to model a
    specific accelerator without losing determinism.

    ``preempt`` — evict the lowest-priority running slot when the page pool
    is exhausted (growth failure or queue-head starvation), resuming later by
    recompute-prefill (default: enabled). The resume is token-for-token
    lossless for every decoding policy: greedy continuation is a pure
    function of the prefix, and seeded sampling re-derives its per-step keys
    from ``fold_in(seed, position)`` over the recomputed prefix
    (``Engine.prefill_into_slot(resume=True)`` restarts verification at the
    exact step boundary the eviction stopped at). ``preempt=False`` stalls
    slots on pool exhaustion instead.

    ``swap`` — swap-to-host preemption (defaults to the engine's
    ``EngineConfig(swap=...)`` setting): an eviction copies the victim's
    pages + per-slot rows to the engine's host pool and the resume becomes
    a device scatter (``Engine.swap_in_slot``) instead of a
    recompute-prefill — bitwise the eviction-time state, so streams are
    unchanged. Per eviction the scheduler picks swap only when the
    bytes-moved cost model says it beats recomputing the prefix —
    ``2 * bytes * swap_cost_per_byte <= prefill_cost +
    prefill_cost_per_token * prefix_tokens`` — AND the host pool can hold
    the snapshot; otherwise (host pool exhausted, or short cheap prefixes)
    it falls back to recompute-prefill, losslessly. ``swap_cost_per_byte``
    / ``prefill_cost_per_token`` extend the virtual clock the same way:
    swap-out/in advance it by bytes moved, admission prefills by
    ``prefill_cost + per-token * prefix`` (both default 0.0 extra —
    existing traces replay bitwise).

    ``adaptive_k`` — per-request dynamic draft length
    (serving/speculation.py): ``True`` enables the
    :class:`SpeculationController` with default knobs, a
    :class:`SpeculationConfig` enables it with those knobs, ``None``/
    ``False`` keeps the fixed ``EngineConfig.K`` (bitwise the
    pre-controller scheduler). When enabled, each request's acceptance EMA
    — keyed by rid, surviving preemption — sets its ``k_row`` at admission
    and at every harvest, and incremental page growth reserves the
    per-row ``k_row + 1`` commit stride instead of the worst-case
    ``K + 1`` (the pool-pressure win). Streams are unchanged for greedy
    requests and stay bitwise deterministic for sampled ones: ``k_row``
    is a pure function of the request's own committed stream.
    """

    def __init__(self, engine: Engine, eos_id: Optional[int] = None,
                 free_on_finish: bool = True, sync_every: int = 1,
                 iter_cost: float = 1.0, prefill_cost: float = 1.0,
                 preempt: Optional[bool] = None,
                 adaptive_k: Any = None,
                 swap: Optional[bool] = None,
                 swap_cost_per_byte: float = 0.0,
                 prefill_cost_per_token: float = 0.0):
        self.engine = engine
        self.eos_id = eos_id
        self.free_on_finish = free_on_finish
        self.sync_every = max(int(sync_every), 1)
        self.iter_cost = float(iter_cost)
        self.prefill_cost = float(prefill_cost)
        self.preempt = True if preempt is None else bool(preempt)
        self.swap = (engine.swap_enabled if swap is None else bool(swap))
        if self.swap and not engine.swap_enabled:
            raise ValueError(
                "Scheduler(swap=True) needs EngineConfig(swap='host')")
        self.swap_cost_per_byte = float(swap_cost_per_byte)
        self.prefill_cost_per_token = float(prefill_cost_per_token)
        if adaptive_k is None or adaptive_k is False:
            self.spec: Optional[SpeculationController] = None
        elif isinstance(adaptive_k, SpeculationController):
            self.spec = adaptive_k
        else:
            cfg = adaptive_k if isinstance(adaptive_k, SpeculationConfig) \
                else None
            self.spec = SpeculationController(engine.ecfg.K, cfg)
        # session state (created by _begin_session; one live session per
        # Scheduler — serve() and a streaming.AsyncEngine each own theirs)
        self._wall_t0: Optional[float] = None
        self._gc: Optional[GcSpans] = None

    # ------------------------------------------------------------------
    # shared loop core — the step/admit/preempt/harvest machinery both
    # drivers call: the deterministic virtual-clock serve() below and the
    # wall-clock streaming.AsyncEngine. Session state lives on the
    # instance between _begin_session() and _end_session(); the only
    # driver-visible difference is who advances self._clock (_advance).
    # ------------------------------------------------------------------
    def _prio(self, r: Request) -> Tuple[float, int]:
        return (r.arrival_time, r._seq)

    @staticmethod
    def _committed_stream(req: Request) -> np.ndarray:
        """prompt + emitted tokens — what a freed slot's pages verifiably
        hold; the engine's prefix cache indexes its full pages so later
        requests (or this one's resume) admit against them."""
        return np.concatenate(
            [req.prompt, np.asarray(req.out_tokens, np.int32)])

    def _begin_session(self) -> None:
        eng = self.engine
        B = eng.batch
        # a prefix-cache engine resumes from the previous session's pool
        # (cached page content lives in the state arrays); otherwise blank
        self._state = eng.serve_state()
        self._active = np.zeros((B,), bool)
        self._max_new = np.zeros((B,), np.int32)
        # per-slot effective draft length (adaptive-K max-K mask); full K
        # when the controller is off — bitwise the pre-adaptive step
        self._k_row = np.full((B,), eng.ecfg.K, np.int32)
        self._slot_req: List[Optional[Request]] = [None] * B
        self._waiting: List[Request] = []     # arrived, sorted by _prio
        self._finished: List[Request] = []    # completed AND aborted
        self._events: List[Tuple[float, str, int]] = []
        self._emit: List[Tuple[Request, List[int], List[float]]] = []
        self._clock = 0.0
        self._n_iters = 0
        self._n_preempt = 0
        self._n_swap = 0            # swap-to-host evictions
        self._n_recompute = 0       # recompute-prefill evictions
        self._n_swap_drop = 0       # handles dropped for pressure relief
        self._recomputed_tokens = 0  # prefix tokens re-fed by resume
        #                              prefills (net of prefix-cache hits)
        self._next_seq = 0
        self._wall_t0 = None        # None → virtual clock (_advance adds)
        # each collection of the session on the trace (host.gc) and counted
        # in the report; the hook is removed by _end_session
        if self._gc is not None:
            self._gc.remove()
        self._gc = GcSpans().install()
        # how the decode step reaches the paged leaves, on the trace clock
        with span("serve.paged_leaves", **eng.paged_leaves):
            pass
        self._t_start = time.perf_counter()

    def _advance(self, cost: float) -> None:
        """Advance the session clock past one unit of work: virtual
        sessions add the deterministic step cost; wall sessions re-read
        elapsed real time (the cost argument is a fiction there)."""
        if self._wall_t0 is None:
            self._clock += cost
        else:
            self._clock = time.perf_counter() - self._wall_t0

    def _event(self, kind: str, rid: int, t: Optional[float] = None) -> None:
        """Append to the event trace, keeping it sorted by time. Almost
        every event is stamped at the current clock (monotone appends); an
        out-of-order stamp — an arrival whose time the idle clock already
        jumped past — is insorted so the trace stays non-decreasing
        (pinned by tests/test_async_serving.py)."""
        t = self._clock if t is None else t
        with span("serve.request." + kind, rid=rid):
            pass                    # the request's event on the trace clock
        ev = (t, kind, rid)
        if self._events and t < self._events[-1][0]:
            bisect.insort(self._events, ev, key=lambda e: e[0])
        else:
            self._events.append(ev)

    def _prepare(self, r: Request, t_submit: Optional[float] = None) -> None:
        """Validate + default-fill one request and assign its FIFO sequence
        number. Raises ValueError before any state is touched."""
        eng = self.engine
        if r.status != QUEUED or r.out_tokens:
            raise ValueError(
                f"request {r.rid} is {r.status}; Request objects are "
                "single-use — submit a fresh one")
        if r.sampling is None:
            r.sampling = eng.ecfg.sampling
        if r.max_new_tokens is None:
            r.max_new_tokens = (r.sampling.max_new_tokens
                                if r.sampling.max_new_tokens is not None
                                else eng.ecfg.max_new_tokens)
        # prompt + budget + worst-case speculative overshoot must fit the
        # cache, else the slot could never reach its budget
        need = (r.prompt.size + eng.pos_offset + r.max_new_tokens
                + eng.ecfg.K + 1)
        if need > eng.ecfg.max_len:
            raise ValueError(
                f"request {r.rid}: prompt {r.prompt.size} + "
                f"max_new_tokens {r.max_new_tokens} (+K overshoot) "
                f"exceeds max_len {eng.ecfg.max_len}")
        if eng.paged:
            n = eng.pages_needed(r.prompt.size, r.max_new_tokens)
            if n > eng.pool_pages:
                raise ValueError(
                    f"request {r.rid}: needs {n} KV pages but the pool "
                    f"only has {eng.pool_pages}; it could never be "
                    "admitted")
        r.t_submit = (time.perf_counter() if t_submit is None else t_submit)
        r._seq = self._next_seq
        self._next_seq += 1
        # freeze the stop set once — _clip_and_check_done runs per sync
        stops = set(r.sampling.stop_token_ids)
        if self.eos_id is not None:
            stops.add(self.eos_id)
        r._stop_set = frozenset(stops)

    def _flush(self, req: Request) -> None:
        """Queue newly FINAL tokens (scanned by _clip_and_check_done, so
        nothing past a stop token or budget — a later sync can never trim
        them) for the streaming driver. The batch driver discards the
        buffer each pass."""
        if len(req.out_tokens) > req._emitted:
            self._emit.append((req, req.out_tokens[req._emitted:],
                               req.out_logprobs[req._emitted:]))
            req._emitted = len(req.out_tokens)

    def _keep_drafts(self, req: Request, s: int) -> None:
        """Merge the draft-log rows slot ``s`` holds for ``req`` (those
        written since its admission) into ``req.drafts``: one slot-row
        readback, made when the request leaves its slot."""
        rows = self.engine.slot_drafts(self._state, s)
        if rows is None:
            return
        if req.drafts is None:
            req.drafts = np.full_like(rows, -1)
        mine = (rows >= 0).any(axis=1)
        req.drafts[mine] = rows[mine]

    def _finish_slot(self, s: int) -> None:
        eng = self.engine
        req = self._slot_req[s]
        self._keep_drafts(req, s)
        req.status = FINISHED
        # wall stamp AFTER device commit: both call sites sit downstream of
        # a blocking host readback of the request's committed tokens (the
        # harvest np.asarray / the admission prefill's last-position read),
        # so sync_every pipelining can't leave the stamped work in flight
        req.t_finish = time.perf_counter()
        req.vt_finish = self._clock
        self._active[s] = False
        self._slot_req[s] = None
        self._finished.append(req)
        if self.spec is not None:
            self.spec.finish(req.rid)
        self._event("finish", req.rid)
        # paged engines MUST free (pages return to the pool); contiguous
        # freeing is cosmetic and stays opt-out
        if self.free_on_finish or eng.paged:
            self._state = eng.free_slot(
                self._state, s, final_tokens=self._committed_stream(req))

    def _abort(self, req: Request) -> bool:
        """Cancel a request NOW: a queued request leaves the wait queue; a
        running one has its slot freed immediately — pages return to the
        pool (free_slot), already-harvested tokens stay valid host-side.
        Returns False when the request already finished/aborted (too late
        to cancel). Only the streaming driver calls this; the batch
        serve() has no cancellation surface."""
        if req.status in (FINISHED, ABORTED):
            return False
        if req.slot is not None:
            s = req.slot
            self._keep_drafts(req, s)
            self._active[s] = False
            self._slot_req[s] = None
            self._state = self.engine.free_slot(
                self._state, s, final_tokens=self._committed_stream(req))
        elif req in self._waiting:
            self._waiting.remove(req)
        # a swapped-out request holds host-pool bytes (and resident page
        # references) while queued — release them NOW, not at drain
        self.engine.drop_swap(req.rid)
        req.status = ABORTED
        req.slot = None
        req.t_finish = time.perf_counter()
        req.vt_finish = self._clock
        self._finished.append(req)
        if self.spec is not None:
            self.spec.finish(req.rid)
        self._event("abort", req.rid)
        return True

    def _swap_beats_recompute(self, req: Request, s: int) -> bool:
        """Swap-vs-recompute policy for evicting slot ``s``: swap when the
        virtual cost of moving the snapshot's bytes BOTH ways is at most
        the cost of re-feeding the committed prefix through a resume
        prefill, and the host pool can actually hold it. With the default
        zero byte cost, swap always wins while the host pool has room —
        the cost model only bites once ``swap_cost_per_byte`` /
        ``prefill_cost_per_token`` are calibrated (table 19 does)."""
        eng = self.engine
        if not self.swap:
            return False
        est = eng.swap_bytes_estimate(s)
        if not eng.host_pool.can_store(est):
            return False        # host pool exhausted → recompute fallback
        prefix = req.prompt.size + len(req.out_tokens)
        return (2.0 * est * self.swap_cost_per_byte
                <= self.prefill_cost
                + self.prefill_cost_per_token * prefix)

    def _preempt_slot(self, s: int) -> None:
        """Evict slot s, re-queueing the request at its original priority.
        Two disciplines: swap-to-host (state parked in the engine's host
        pool, resume is a device scatter) when enabled and worth it under
        the bytes-vs-tokens cost model, else recompute-prefill (pages
        freed, prompt + generated tokens retained host-side, prefix
        re-fed at resume). Both are token-for-token lossless; the swap
        path additionally skips re-paying the prefill FLOPs."""
        eng = self.engine
        req = self._slot_req[s]
        self._keep_drafts(req, s)
        swapped = False
        if self._swap_beats_recompute(req, s):
            self._state, swapped = eng.swap_out_slot(self._state, s, req.rid)
        req.status = QUEUED
        req.slot = None
        req.n_preempt += 1
        req._iters_base = req.iters
        self._n_preempt += 1
        self._active[s] = False
        self._slot_req[s] = None
        if swapped:
            req.n_swap += 1
            self._n_swap += 1
            self._advance(self.swap_cost_per_byte * eng.swap_last_bytes)
            self._event("swap_out", req.rid)
        else:
            self._n_recompute += 1
            self._state = eng.free_slot(
                self._state, s, final_tokens=self._committed_stream(req))
            self._event("preempt", req.rid)
        bisect.insort(self._waiting, req, key=self._prio)

    def _drop_one_swap(self, exclude: Optional[Request] = None) -> bool:
        """Pressure relief of last resort. A swap handle pins its resident
        (cache-shared) pages at refcount >= 2, where a recompute eviction
        would have left them evictable — so a device pool wedged behind
        swapped prefixes must degrade to the recompute discipline, never
        deadlock: drop the LOWEST-priority swapped handle (that request
        resumes by recompute-prefill, still lossless) and let the caller
        re-try admission/growth. Returns False when nothing is droppable."""
        eng = self.engine
        cands = [r for r in self._waiting
                 if r is not exclude and eng.has_swap(r.rid)]
        if not cands:
            return False
        victim = max(cands, key=self._prio)
        eng.drop_swap(victim.rid)
        self._n_swap_drop += 1
        self._event("swap_drop", victim.rid)
        return True

    def _lowest_prio_active(self) -> Optional[int]:
        live = [s for s in range(self.engine.batch) if self._active[s]]
        if not live:
            return None
        return max(live, key=lambda s: self._prio(self._slot_req[s]))

    def _head_admissible(self, req: Request) -> bool:
        # resumed requests gate on their full remaining need (anti-
        # thrash: a victim must not be re-evicted by the pressure that
        # evicted it); fresh ones on the initial claim only. The
        # admission prompt is passed along so a prefix-cache engine
        # gates on the EFFECTIVE need — pages the prompt will map from
        # the cache never touch the free list. ``resume`` mirrors the
        # prefill_into_slot flag so the gate prices the exact claim (a
        # no-commit sampled resume needs one position less —
        # Engine.initial_pages)
        eng = self.engine
        plen = req.prompt.size + len(req.out_tokens)
        rem = req.max_new_tokens - len(req.out_tokens)
        if eng.has_swap(req.rid):
            # swapped resume: priced at its DEVICE-page need only — fresh
            # pages for the host spans (+ remaining lifetime growth under
            # the full gate); resident pages are already on device
            return eng.can_swap_in(req.rid, plen, rem,
                                   full=req.n_preempt > 0)
        stream = req.prompt
        resume = False
        if req.out_tokens:
            stream = self._committed_stream(req)
            if not req.sampling.is_greedy:
                stream = stream[:-1]   # sampled resume prefills [:-1]
                resume = True
        return eng.can_admit(plen, rem, full=req.n_preempt > 0,
                             tokens=stream, resume=resume)

    def _clip_and_check_done(self, req: Request) -> bool:
        """Trim at the first stop token (scheduler ``eos_id`` or the
        request's ``SamplingParams.stop_token_ids``) / budget; True when
        the request is complete.

        Incremental: only tokens appended since the previous call are
        scanned (the ``req._scanned`` cursor) — a stop token can never
        survive an earlier scan, so this equals the full rescan at O(n)
        total work per stream instead of O(n²). It is also what makes
        streaming sound: every position below ``_scanned`` is FINAL
        (no later sync trims at or before it), so _flush may emit exactly
        that prefix and never retract a token."""
        out = req.out_tokens
        done = False
        for i in range(req._scanned, len(out)):
            if out[i] in req._stop_set:
                del out[i + 1:]
                del req.out_logprobs[i + 1:]
                done = True
                break
        if len(out) >= req.max_new_tokens:
            del out[req.max_new_tokens:]         # speculative overshoot
            del req.out_logprobs[req.max_new_tokens:]
            done = True
        req._scanned = len(out)
        return done

    def _swap_admit(self, req: Request, s: int) -> None:
        """Resume a swapped-out request: scatter its host snapshot back
        into (empty) slot ``s`` — no prefill, no re-sampling, the restored
        state is bitwise the eviction-time step boundary for every
        decoding policy. Mirrors the resume conventions of ``_admit``:
        committed counters restart at 0 against the remaining budget."""
        eng = self.engine
        remaining = req.max_new_tokens - len(req.out_tokens)
        req.status = PREFILLING
        req.slot = s
        self._state, last = eng.swap_in_slot(self._state, s, req.rid)
        self._advance(self.swap_cost_per_byte * eng.swap_last_bytes)
        self._event("swap_in", req.rid)
        req._prev_new, req._prev_last = 0, last
        req.status = DECODING
        self._slot_req[s] = req
        self._active[s] = True
        self._max_new[s] = remaining
        if self.spec is not None:
            self._k_row[s] = self.spec.k_for(req.rid)

    def _admit(self, req: Request, s: int) -> None:
        eng = self.engine
        if eng.has_swap(req.rid):
            self._swap_admit(req, s)
            return
        # recompute-prefill resume: the prefix is prompt + everything
        # generated before eviction. Greedy continuation from that
        # prefix is exactly the uninterrupted stream (the prefill's
        # argmax commit equals the verify path's token); a sampled
        # request instead resumes via resume=True — the prefill rebuilds
        # the eviction's step-boundary state and commits nothing new, so
        # the next step restarts seeded verification at the same
        # committed prefix — and fold_in key — the uninterrupted run's
        # step boundary had
        prompt = (self._committed_stream(req) if req.out_tokens
                  else req.prompt)
        resume = bool(req.out_tokens) and not req.sampling.is_greedy
        remaining = req.max_new_tokens - len(req.out_tokens)
        req.status = PREFILLING
        req.slot = s
        first_admission = req.vt_admit is None
        if first_admission:
            req.vt_admit = self._clock
        extras = req.extras
        if extras is None and eng.tcfg.family in ("vlm", "encdec"):
            # deterministic stub frontend inputs keyed by the PROMPT
            # (not the process-global rid), so re-serving the same
            # workload with fresh Request objects replays identical
            # extras; cached on the request so a preemption resume
            # (longer recompute prompt) also replays them
            seed = zlib.crc32(req.prompt.tobytes()) & 0x7FFFFFFF
            extras = make_extras(eng.tcfg, 1, "prefill",
                                 jax.random.fold_in(jax.random.PRNGKey(0),
                                                    seed))
            req.extras = extras
        self._event("admit", req.rid)
        with span("serve.prefill"):
            self._state, first, last = eng.prefill_into_slot(
                self._state, prompt, s, extras=extras,
                sampling=req.sampling, max_new=remaining, resume=resume)
        if first_admission:
            # wall stamp AFTER the prefill: prefill_into_slot's host
            # readback of the committed position sequences every queued
            # device dispatch before it, so t_admit marks work actually
            # committed, not an enqueue (the virtual vt_admit keeps the
            # admission-decision timestamp)
            req.t_admit = time.perf_counter()
        req.cached_tokens += eng.last_hit_tokens
        if req.n_preempt:
            # prefix positions this resume actually re-forwarded (net of
            # prefix-cache hits) — the FLOP bill swap-to-host avoids
            self._recomputed_tokens += max(
                int(prompt.size) - eng.last_hit_tokens, 0)
        self._advance(self.prefill_cost
                      + self.prefill_cost_per_token * int(prompt.size))
        if first is None:               # no-commit resume (sampled)
            req._prev_new, req._prev_last = 0, last
        else:
            req.out_tokens.append(first)
            req.out_logprobs.append(eng.last_logprob)
            req._committed += 1
            req._prefills += 1
            req._prev_new, req._prev_last = 1, last
        req.status = DECODING
        self._slot_req[s] = req
        self._active[s] = True
        self._max_new[s] = remaining
        if self.spec is not None:
            # rid-keyed: a resume continues from the acceptance state the
            # stream had at eviction, a fresh rid starts optimistic
            self._k_row[s] = self.spec.k_for(req.rid)
        done = self._clip_and_check_done(req)
        self._flush(req)
        if done:                         # EOS at the very first token
            self._finish_slot(s)

    def _admit_waiting(self) -> None:
        """Admit eligible requests into free slots, FIFO by (arrival,
        submission) with head-of-line blocking; preemption resolves
        starvation when the head outranks a runner. Free slots are
        recomputed per admission — a slot freed by a preemption (or an
        EOS-at-prefill) is reusable immediately, not after the next sync
        block."""
        with span("serve.admit"):
            B = self.engine.batch
            while self._waiting:
                free = [s for s in range(B) if not self._active[s]
                        and self._slot_req[s] is None]
                if not free:
                    break
                head = self._waiting[0]
                if not self._head_admissible(head):
                    if self.preempt:
                        while not self._head_admissible(head):
                            v = self._lowest_prio_active()
                            if v is None or (self._prio(self._slot_req[v])
                                             <= self._prio(head)):
                                break
                            self._preempt_slot(v)
                    # swap handles pin resident pages a recompute eviction
                    # would have released — drop lower-priority handles until
                    # the head fits, so swap can only ever ADD admissible
                    # schedules, never wedge one. (Dropping the head's OWN
                    # handle never helps: a swapped resume needs at most the
                    # pages its recompute twin would, so it stays excluded.)
                    while (not self._head_admissible(head)
                           and self._drop_one_swap(exclude=head)):
                        pass
                    if not self._head_admissible(head):
                        break                # head waits for frees (FIFO)
                self._admit(self._waiting.pop(0), free[0])

    def _grow(self) -> np.ndarray:
        """Capacity pass: grow each live slot to cover the coming sync
        block (incremental paged growth); on pool exhaustion preempt the
        lowest-priority slot, or stall when preemption is off. Returns the
        run mask; raises when nothing can step at all."""
        with span("serve.grow"):
            eng = self.engine
            B = eng.batch
            stalled = np.zeros((B,), bool)
            if eng.incremental:
                by_prio = sorted(np.flatnonzero(self._active),
                                 key=lambda s: self._prio(self._slot_req[s]))
                for s in by_prio:
                    if not self._active[s]:      # already evicted this pass
                        continue
                    req = self._slot_req[s]
                    cap = (req.prompt.size + eng.pos_offset
                           + req.max_new_tokens + eng.ecfg.K + 1)
                    # a step at position c writes KV c..c+stride-1 and moves
                    # c by at most stride, so sync_every steps need length
                    # last + sync_every*stride, exactly. Under adaptive K the
                    # row's stride is k_row + 1, not the worst-case K + 1 —
                    # a hard row reserves (and can be preempted for) fewer
                    # pages. Writes past the row's allocation are dropped by
                    # scatter and equivalent to commit-invalidated entries,
                    # so the shorter reservation stays bitwise lossless.
                    if self.spec is not None \
                            and eng.ecfg.drafter_mode != "none":
                        stride = int(self._k_row[s]) + 1
                    else:
                        stride = eng.commit_stride
                    target = min(req._prev_last + self.sync_every * stride,
                                 cap)
                    self._state, ok = eng.ensure_capacity(self._state, int(s),
                                                          target)
                    while not ok and self.preempt:
                        v = self._lowest_prio_active()
                        self._preempt_slot(v)
                        if v == s:
                            break
                        self._state, ok = eng.ensure_capacity(self._state,
                                                              int(s), target)
                    while not ok and self._active[s] \
                            and self._drop_one_swap():
                        # growth wedged behind handle-pinned pages: fall
                        # swapped waiters back to recompute and retry
                        self._state, ok = eng.ensure_capacity(self._state,
                                                              int(s), target)
                    if not ok and self._active[s]:
                        stalled[s] = True        # retry once pages free up
            run = self._active & ~stalled
            if not run.any():
                raise RuntimeError(
                    "page pool exhausted and every live slot is stalled; "
                    "enable preemption (Scheduler(preempt=True)) or grow "
                    "pool_pages")
            return run

    def _dispatch(self, run: np.ndarray) -> None:
        """sync_every speculative iterations over the live slots (jax
        pipelines the dispatches; budget freezes happen on device
        regardless)."""
        with span("serve.dispatch"):
            eng = self.engine
            act_dev, mn_dev = jnp.asarray(run), jnp.asarray(self._max_new)
            kr_dev = jnp.asarray(self._k_row)
            for _ in range(self.sync_every):
                self._state = eng.step(self._state, act_dev, mn_dev, kr_dev)
                self._n_iters += 1
                self._advance(self.iter_cost)

    def _harvest(self) -> None:
        """Read back the per-slot counters + newly committed tokens and
        logprobs, stop/budget-trim each stream (incremental scan), flush
        final tokens to the emit buffer, retire finished slots. The
        np.asarray readbacks block on every dispatched step, so wall
        stamps taken downstream mark committed work."""
        state = self._state
        with span("serve.readback"):
            new_count = np.asarray(state["new_count"])
            slot_iters = np.asarray(state["slot_iters"])
            last = np.asarray(state["last"])
            tokens = np.asarray(state["tokens"])
            logprobs = np.asarray(state["logprobs"])
        with span("serve.harvest"):
            for s in range(self.engine.batch):
                req = self._slot_req[s]
                if req is None or not self._active[s]:
                    continue
                prev_iters, prev_comm = req.iters, req._committed
                req.iters = req._iters_base + int(slot_iters[s])
                if new_count[s] > req._prev_new:
                    lo, hi = req._prev_last + 1, last[s] + 1
                    req.out_tokens.extend(tokens[s, lo:hi].tolist())
                    req.out_logprobs.extend(
                        logprobs[s, lo:hi].astype(float).tolist())
                    req._committed += int(new_count[s]) - req._prev_new
                    req._prev_new = int(new_count[s])
                    req._prev_last = int(last[s])
                if self.spec is not None:
                    # fold THIS request's decode delta (committed tokens over
                    # engine iterations since the last sync) into its
                    # acceptance EMA and refresh the slot's draft length;
                    # zero-iteration windows (frozen rows) carry no signal
                    d_it = req.iters - prev_iters
                    if d_it > 0:
                        self.spec.observe(req.rid, req._committed - prev_comm,
                                          d_it)
                        self._k_row[s] = self.spec.k_for(req.rid)
                done = self._clip_and_check_done(req)
                self._flush(req)
                if done:
                    self._finish_slot(s)

    def _end_session(self, wall: float) -> Dict[str, Any]:
        # keep cached pages warm across serves
        self.engine.retain_state(self._state)
        self._gc.remove()
        return self._report(self._finished, wall, self._n_iters,
                            self._clock, self._events, self._n_preempt)

    # ------------------------------------------------------------------
    def serve(self, requests: Sequence,
              max_iters: int = 100_000) -> Dict[str, Any]:
        """Run every request to completion; returns aggregate + per-request
        metrics (wall-clock and virtual-time). ``requests`` entries may be
        Request objects or raw prompt arrays (coerced with the engine's
        default budget and sampling policy, arrival 0).

        This is the deterministic VIRTUAL-CLOCK driver of the shared loop
        core (admit → grow → dispatch → harvest); the wall-clock streaming
        twin is serving/streaming.AsyncEngine. Identical per-request token
        streams either way — row independence plus per-request seeded
        sampling make each stream a pure function of (prompt, policy),
        never of driver timing."""
        reqs = [r if isinstance(r, Request) else Request(r) for r in requests]
        self._begin_session()
        for r in reqs:
            self._prepare(r, t_submit=self._t_start)
        pending = deque(sorted(reqs, key=self._prio))   # not yet arrived

        while pending or self._waiting or self._active.any():
            # ---- arrivals: move everything whose time has come -----------
            # (the arrive event is stamped at the true arrival_time, which
            # the idle clock may already have jumped past — _event insorts
            # it so the trace stays time-sorted)
            while pending and pending[0].arrival_time <= self._clock + 1e-9:
                r = pending.popleft()
                bisect.insort(self._waiting, r, key=self._prio)
                self._event("arrive", r.rid, t=r.arrival_time)
            # ---- idle: nothing eligible, nothing running → jump the clock
            if not self._waiting and not self._active.any():
                self._clock = max(self._clock, pending[0].arrival_time)
                continue

            self._admit_waiting()
            if not self._active.any():
                if self._waiting:
                    raise RuntimeError(
                        "no active slot and the head request cannot be "
                        "admitted — page pool leak?")
                continue                     # everything died at prefill

            run = self._grow()
            self._dispatch(run)
            if self._n_iters > max_iters:
                raise RuntimeError("scheduler exceeded max_iters")
            self._harvest()
            self._emit.clear()               # batch driver: nobody streams

        wall = time.perf_counter() - self._t_start
        return self._end_session(wall)

    # ------------------------------------------------------------------
    def _report(self, finished: List[Request], wall: float, n_iters: int,
                makespan_vt: float, events: List[Tuple[float, str, int]],
                n_preempt: int) -> Dict[str, Any]:
        """Aggregate + per-request metrics. Clock columns, honestly:

        - ``*_s`` — HOST WALL stamps. t_admit is taken after the admission
          prefill's committed-position readback and t_finish after the
          harvest readback of the finishing sync, so both mark device work
          that actually committed (never a queued dispatch); resolution is
          the sync boundary (``sync_every`` iterations).
        - ``*_vt`` — the deterministic clock: virtual step-cost units under
          serve() (bit-identical across replays), wall seconds since
          session start under the streaming driver (same code path, the
          clock source is real time there).

        Aborted requests (streaming driver only) appear in ``results`` with
        ``aborted: True`` and their partial output; aggregate latency/AL
        stats cover completed requests only, token totals cover both (the
        work was done either way)."""
        results = [{
            "rid": r.rid,
            "tokens": np.asarray(r.out_tokens, np.int32),
            "logprobs": np.asarray(r.out_logprobs, np.float32),
            "n_new": len(r.out_tokens),
            "iters": r.iters,
            "acceptance_length": r.acceptance_length,
            "arrival_time": r.arrival_time,
            "n_preempt": r.n_preempt,
            "n_swap": r.n_swap,
            "cached_tokens": r.cached_tokens,
            "aborted": r.status == ABORTED,
            "wait_s": r.t_admit - r.t_submit,
            "latency_s": r.t_finish - r.t_submit,
            "wait_vt": (r.vt_admit - r.arrival_time
                        if r.vt_admit is not None else float("nan")),
            "latency_vt": r.vt_finish - r.arrival_time,
            **({"k_final":
                self.spec.request_report(r.rid)["k_final"]}
               if self.spec is not None else {}),
            **({"drafts": self._draft_rows(r)}
               if self.engine.ecfg.drafter_mode != "none" else {}),
        } for r in sorted(finished, key=lambda r: r.rid)]
        total = sum(r["n_new"] for r in results)
        done = [r for r in results if not r["aborted"]]
        lat_vt = [r["latency_vt"] for r in done] or [0.0]
        wait_vt = [r["wait_vt"] for r in done
                   if not np.isnan(r["wait_vt"])] or [0.0]
        # iteration-WEIGHTED acceptance length: total decode-committed
        # tokens over total decode iterations (completed requests). The
        # per-request mean stays alongside, but a 1-iteration straggler
        # must not weigh the same as a 500-iteration stream — benchmarks
        # report this aggregate.
        done_reqs = [r for r in finished if r.status == FINISHED]
        dec_tok = sum(r._committed - r._prefills for r in done_reqs)
        dec_it = sum(r.iters for r in done_reqs)
        hp = self.engine.host_pool            # None unless swap="host"
        return {
            "results": results,
            "n_requests": len(results),
            "iterations": n_iters,
            "total_new_tokens": total,
            "wall_s": wall,
            "otps": total / max(wall, 1e-9),
            "mean_acceptance_length": float(np.mean(
                [r["acceptance_length"] for r in done])) if done else 0.0,
            "weighted_acceptance_length": dec_tok / max(dec_it, 1),
            **({"speculation": self.spec.report()}
               if self.spec is not None else {}),
            "mean_latency_s": float(np.mean(
                [r["latency_s"] for r in done])) if done else 0.0,
            # deterministic-clock latency profile + churn trace
            "makespan_vt": makespan_vt,
            "otps_vt": total / max(makespan_vt, 1e-9),
            "preemptions": n_preempt,
            # preemption-kind split (honest degradation accounting): every
            # eviction is exactly one of swap-to-host or recompute-prefill;
            # swap_drops counts handles later demoted to recompute under
            # pressure relief, and recomputed_prefill_tokens is the prefix
            # FLOP bill the recompute resumes actually re-paid
            "preempt_swap": self._n_swap,
            "preempt_recompute": self._n_recompute,
            "swap_drops": self._n_swap_drop,
            "recomputed_prefill_tokens": self._recomputed_tokens,
            "host_pool": {
                # `is not None`: an empty HostPagePool is falsy (__len__)
                "capacity_bytes": hp.capacity if hp is not None else 0,
                "used_bytes": hp.used_bytes if hp is not None else 0,
                "peak_bytes": hp.peak_used if hp is not None else 0,
            },
            # device-pool high-water mark (0 for contiguous engines) — read
            # AFTER Engine.reset_stats() between phases for per-phase peaks
            "peak_pages": (self.engine.allocator.peak_used
                           if self.engine.paged else 0),
            "aborted": len(results) - len(done),
            # prefix-cache effectiveness (0s on cache-off engines)
            "cache_hit_tokens": sum(r["cached_tokens"] for r in results),
            "cache_hit_requests": sum(
                1 for r in results if r["cached_tokens"] > 0),
            "p50_latency_vt": float(np.percentile(lat_vt, 50)),
            "p99_latency_vt": float(np.percentile(lat_vt, 99)),
            "p50_wait_vt": float(np.percentile(wait_vt, 50)),
            "p99_wait_vt": float(np.percentile(wait_vt, 99)),
            "events": events,
            # the session's garbage collections (each a host.gc span on
            # the trace) and the seconds they took
            "gc_collections": self._gc.collections,
            "gc_s": self._gc.seconds,
            # paged leaves the decode step reads and writes in place, and
            # those it still gathers into the per-slot view (0 and 0 off
            # the paged layout; Engine.paged_leaves)
            "paged_in_place": self.engine.paged_leaves["in_place"],
            "paged_gathered": self.engine.paged_leaves["gathered"],
        }

    def _draft_rows(self, r: Request) -> np.ndarray:
        """``r``'s draft log over its committed stream: (positions, K),
        row c the drafts proposed from stream position c, -1 where none
        was (prompt positions, and positions an accepted draft skipped)."""
        n = r.prompt.size + self.engine.pos_offset + len(r.out_tokens)
        if r.drafts is None:
            return np.full((n, self.engine.ecfg.K), -1, np.int32)
        return r.drafts[:n].copy()


class LLMEngine:
    """vLLM-style front-end over Engine + Scheduler: offline batch
    generation with per-prompt :class:`SamplingParams`.

    Quickstart::

        llm = LLMEngine(engine, eos_id=2)
        outs = llm.generate(prompts, SamplingParams(temperature=0.8, seed=7))
        outs[0]["tokens"]            # np.int32 generated ids, stop-trimmed

    ``generate`` accepts one ``SamplingParams`` for every prompt or a list
    with one entry per prompt (None entries fall back to the engine
    default), so a single call — and a single batch — may mix greedy and
    sampled requests. Outputs are returned in prompt order; the full
    scheduler report of the last call (aggregate OTPS, latency percentiles,
    event trace) is kept on ``last_report``.
    """

    def __init__(self, engine: Engine, eos_id: Optional[int] = None,
                 **scheduler_kwargs):
        self.engine = engine
        self.scheduler = Scheduler(engine, eos_id=eos_id, **scheduler_kwargs)
        self.last_report: Optional[Dict[str, Any]] = None

    def generate(self, prompts: Sequence,
                 sampling_params=None) -> List[Dict[str, Any]]:
        """Generate a completion for every prompt; returns one result dict
        per prompt (``tokens``, ``n_new``, ``acceptance_length``, ...) in
        prompt order."""
        n = len(prompts)
        if sampling_params is None or isinstance(sampling_params,
                                                 SamplingParams):
            sampling_params = [sampling_params] * n
        if len(sampling_params) != n:
            raise ValueError(
                f"{len(sampling_params)} sampling_params for {n} prompts")
        reqs = [Request(p, sampling=sp)
                for p, sp in zip(prompts, sampling_params)]
        order = {r.rid: i for i, r in enumerate(reqs)}
        self.last_report = self.scheduler.serve(reqs)
        return sorted(self.last_report["results"],
                      key=lambda res: order[res["rid"]])


def serve_round_based(engine: Engine, prompts: Sequence,
                      budgets: Optional[Sequence[int]] = None,
                      batch: Optional[int] = None) -> Dict[str, Any]:
    """The pre-scheduler baseline (previously examples/serve_batched.py's
    ``serve_queue``): fixed batch slots, queue refilled only *between* full
    generation rounds — a finished row idles until the round's slowest member
    drains. Honors per-request ``budgets`` (rows freeze on device at their
    own max_new, like HF-generate-style static batching with early stop) so
    benchmarks/table11_continuous.py compares the two disciplines on the
    same workload."""
    batch = batch or engine.batch
    default = engine.ecfg.max_new_tokens
    queue = [np.asarray(p, np.int32) for p in prompts]
    buds = list(budgets) if budgets is not None else [default] * len(queue)
    toks, rounds, al_num, al_den = 0, 0, 0, 0
    t0 = time.perf_counter()
    while queue:
        cur, queue = queue[:batch], queue[batch:]
        bud, buds = buds[:len(cur)], buds[len(cur):]
        n_real = len(cur)
        while len(cur) < batch:                  # pad final round
            cur.append(cur[-1])
            bud.append(0)                        # padded rows stay frozen
        state = engine.prefill(jnp.stack(cur))
        max_new = jnp.asarray(np.maximum(bud, 1), jnp.int32)
        it = 0
        while True:
            state = engine.step(state, max_new=max_new)
            it += 1
            if it % 4 == 0 or it < 2:
                nc = np.asarray(state["new_count"])
                if (nc >= np.asarray(bud))[:n_real].all():
                    break
        # nc already holds a post-break readback: the poll loop only exits
        # through the branch that just refreshed it — don't sync again
        nc = nc[:n_real]
        toks += int(np.minimum(nc, bud[:n_real]).sum())  # trim overshoot
        al_num += int(np.asarray(state["committed"]))
        al_den += max(int(np.asarray(state["row_iters"])), 1)
        rounds += 1
    wall = time.perf_counter() - t0
    return {
        "otps": toks / max(wall, 1e-9),
        "total_new_tokens": toks,
        "wall_s": wall,
        "mean_acceptance_length": al_num / max(al_den, 1),
        "rounds": rounds,
    }
