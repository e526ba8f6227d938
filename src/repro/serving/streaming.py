"""Wall-clock streaming front-end over the Scheduler's shared loop core.

``serving/scheduler.py`` owns the step/admit/preempt/harvest machinery and
drives it two ways: the deterministic virtual-clock ``Scheduler.serve``
(batch, replayable, what every losslessness/churn test pins) and THIS
module's :class:`AsyncEngine` — the same core methods, paced by real time
and asyncio, streaming each request's ``(token, logprob)`` pairs out as
speculative syncs commit::

                 ┌──────────────── shared loop core ────────────────┐
                 │  _admit_waiting → _grow → _dispatch → _harvest   │
                 └───────▲──────────────────────────────▲───────────┘
          virtual clock  │                              │  wall clock
      Scheduler.serve()  │                              │  AsyncEngine._run()
      (deterministic twin; batch report)     (asyncio; streams the emit
                                              buffer, accepts abort())

Because every request's token stream is a pure function of its own
``(prompt, SamplingParams)`` — row independence through attention/caches,
per-request ``fold_in(seed, position)`` keys — a streamed run yields
token-for-token exactly what the virtual-clock twin produces for the same
workload, regardless of arrival timing, batch composition, preemptions, or
aborts of OTHER requests (tests/test_streaming.py pins this).

Streaming semantics:

- ``generate()`` yields only FINAL tokens: the emit buffer is filled after
  the incremental stop/budget trim (``_clip_and_check_done``), so nothing
  past a stop token or budget is ever yielded, and a yielded token is
  never retracted.
- ``abort()`` (or closing a ``generate()`` iterator early) cancels a
  request immediately: a queued request leaves the wait queue; a running
  one's pages return to the pool via the ordinary ``free_slot`` path
  before the next sync, so the slot is reusable at once. Aborting a
  swapped-out request (``EngineConfig(swap="host")``) additionally frees
  its host bytes right away — the HostPagePool never holds state for a
  dead request.
- Backpressure: at most ``max_pending`` requests may be in flight
  (queued + running); ``submit()``/``generate()`` await a free admission
  ticket. ``health()`` reports queue depth, running slots, pool occupancy
  and wait percentiles for monitoring.

The process-separated NDJSON socket front-end lives in
``launch/serve_stream.py``; this class is the in-process API it wraps.
"""
from __future__ import annotations

import asyncio
import bisect
import time
from typing import Any, AsyncIterator, Dict, Optional, Tuple

import numpy as np

from repro.serving.engine import Engine
from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import (ABORTED, FINISHED, Request, Scheduler)
from repro.tracing import span


class StreamHandle:
    """One in-flight streamed request: an async iterator of
    ``(token, logprob)`` pairs plus ``abort()``. Obtained from
    :meth:`AsyncEngine.submit`; :meth:`AsyncEngine.generate` wraps one."""

    def __init__(self, engine: "AsyncEngine", request: Request,
                 queue: "asyncio.Queue"):
        self._engine = engine
        self.request = request
        self._queue = queue
        self._exhausted = False

    @property
    def rid(self) -> int:
        return self.request.rid

    @property
    def done(self) -> bool:
        """Finished or aborted — no further tokens will arrive."""
        return self.request.status in (FINISHED, ABORTED)

    @property
    def aborted(self) -> bool:
        return self.request.status == ABORTED

    def abort(self) -> bool:
        """Cancel this request (idempotent); see AsyncEngine.abort."""
        return self._engine.abort(self)

    def __aiter__(self) -> "StreamHandle":
        return self

    async def __anext__(self) -> Tuple[int, float]:
        if self._exhausted:
            raise StopAsyncIteration
        item = await self._queue.get()
        if item is None:                      # finish/abort sentinel
            self._exhausted = True
            raise StopAsyncIteration
        if isinstance(item, BaseException):   # dispatch loop died
            self._exhausted = True
            raise item
        return item


class AsyncEngine:
    """Wall-clock asyncio serving engine over one :class:`Engine`.

    Owns a private :class:`Scheduler` session driven by a background
    dispatch task; everything — admissions, speculative steps, harvests,
    aborts — runs on the one event loop, so core state never needs locks
    (client-facing calls only touch it at the loop's await boundaries).

    Args:
      engine: the (typically paged) serving Engine. Exclusive: don't drive
        the same Engine from ``Scheduler.serve`` while a session is open.
      eos_id / sync_every / preempt / free_on_finish / adaptive_k:
        forwarded to the underlying Scheduler (same semantics as the
        batch driver; ``adaptive_k`` enables the per-request dynamic-K
        speculation controller, serving/speculation.py).
      max_pending: admission-ticket bound — submitted-but-unfinished
        requests beyond this block in ``submit()`` until something
        finishes or aborts (default ``4 * engine.batch``).

    Quickstart::

        aeng = AsyncEngine(engine, eos_id=2)
        async for tok, lp in aeng.generate(prompt,
                                           SamplingParams(temperature=0.8,
                                                          seed=7)):
            ...                        # arrives as each sync commits
        aeng.health()["queue_depth"]
        report = await aeng.close()    # Scheduler-style aggregate report
    """

    def __init__(self, engine: Engine, eos_id: Optional[int] = None,
                 sync_every: int = 1, preempt: Optional[bool] = None,
                 free_on_finish: bool = True,
                 max_pending: Optional[int] = None,
                 adaptive_k: Any = None):
        self.engine = engine
        self.scheduler = Scheduler(engine, eos_id=eos_id,
                                   free_on_finish=free_on_finish,
                                   sync_every=sync_every, preempt=preempt,
                                   adaptive_k=adaptive_k)
        self.max_pending = (int(max_pending) if max_pending
                            else 4 * engine.batch)
        if self.max_pending < 1:
            raise ValueError("max_pending must be >= 1")
        self._queues: Dict[int, asyncio.Queue] = {}
        self._done: set = set()          # rids whose sentinel was delivered
        self._inflight = 0
        self._n_fin = 0                  # _finished entries already delivered
        self._task: Optional[asyncio.Task] = None
        self._sem: Optional[asyncio.Semaphore] = None
        self._wake: Optional[asyncio.Event] = None
        self._closing = False
        self._error: Optional[BaseException] = None
        self._report: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    async def start(self) -> None:
        """Open the serving session and start the dispatch loop (idempotent;
        ``submit`` calls it lazily)."""
        if self._task is not None:
            return
        sched = self.scheduler
        sched._begin_session()
        # wall-clock mode: _advance re-reads elapsed real time, so the
        # session's *_vt columns and event stamps are wall seconds
        sched._wall_t0 = sched._t_start
        self._sem = asyncio.Semaphore(self.max_pending)
        self._wake = asyncio.Event()
        self._task = asyncio.get_running_loop().create_task(self._run())

    async def close(self, drain: bool = True) -> Dict[str, Any]:
        """Shut the session down and return the Scheduler-style aggregate
        report. ``drain=True`` first waits for every in-flight request;
        ``drain=False`` aborts them."""
        if self._task is None:
            await self.start()           # trivial empty session
        if not drain:
            for req in list(self.scheduler._waiting):
                self.abort(req)
            for req in list(self.scheduler._slot_req):
                if req is not None:
                    self.abort(req)
        self._closing = True
        self._wake.set()
        await self._task
        if self._report is None:
            sched = self.scheduler
            self._report = sched._end_session(
                time.perf_counter() - sched._t_start)
        if self._error is not None:
            raise self._error
        return self._report

    # ------------------------------------------------------------------
    # client API
    # ------------------------------------------------------------------
    async def submit(self, prompt, sampling_params: Optional[SamplingParams]
                     = None, max_new_tokens: Optional[int] = None,
                     extras: Optional[dict] = None) -> StreamHandle:
        """Admission-gated submit: awaits a backpressure ticket, then
        enqueues the request and returns its :class:`StreamHandle`.
        Raises ValueError (before consuming a ticket slot) for requests
        that could never be served (budget exceeds max_len / pool)."""
        await self.start()
        if self._error is not None:
            raise self._error
        if self._closing:
            raise RuntimeError("AsyncEngine is closing")
        await self._sem.acquire()
        sched = self.scheduler
        try:
            if self._error is not None:
                raise self._error
            sched._advance(0.0)          # refresh the wall clock
            req = Request(prompt, max_new_tokens=max_new_tokens,
                          arrival_time=sched._clock, extras=extras,
                          sampling=sampling_params)
            sched._prepare(req)          # ValueError → ticket returned
        except BaseException:
            self._sem.release()
            raise
        self._inflight += 1
        q: asyncio.Queue = asyncio.Queue()
        self._queues[req.rid] = q
        bisect.insort(sched._waiting, req, key=sched._prio)
        sched._event("arrive", req.rid)
        self._wake.set()
        return StreamHandle(self, req, q)

    async def generate(self, prompt,
                       sampling_params: Optional[SamplingParams] = None,
                       max_new_tokens: Optional[int] = None,
                       extras: Optional[dict] = None
                       ) -> AsyncIterator[Tuple[int, float]]:
        """Stream one completion: yields ``(token, logprob)`` as each
        speculative sync commits (stop/budget-trimmed — never a token past
        the stop). Closing the iterator early aborts the request, freeing
        its slot immediately."""
        handle = await self.submit(prompt, sampling_params, max_new_tokens,
                                   extras)
        try:
            async for tok, lp in handle:
                yield tok, lp
        finally:
            if not handle.done:
                self.abort(handle)

    def abort(self, handle) -> bool:
        """Cancel a request (StreamHandle or Request) right now. Pages are
        freed through the ordinary free_slot path, so the slot is
        admissible again on the very next loop pass; tokens already
        streamed remain valid. Returns False when the request had already
        finished. Safe to call from any coroutine on the engine's loop —
        the dispatch loop only runs core mutations between awaits."""
        req = handle.request if isinstance(handle, StreamHandle) else handle
        sched = self.scheduler
        if self._task is None:
            return False
        sched._advance(0.0)
        if not sched._abort(req):
            return False
        self._deliver()                  # sentinel + ticket release
        self._wake.set()
        return True

    def health(self) -> Dict[str, Any]:
        """Monitoring snapshot of the live session (cheap, host-side)."""
        sched, eng = self.scheduler, self.engine
        if self._task is None:
            raise RuntimeError("AsyncEngine not started")
        completed = [r for r in sched._finished if r.status == FINISHED]
        # wait list and filter use the SAME clock: the list reads the wall
        # stamps (t_admit - t_submit), so never-admitted requests are
        # screened by the wall stamp too (t_admit == 0.0 means the request
        # finished/aborted without ever being admitted — mixing in the
        # virtual vt_admit here would conflate the two clocks PR 7 split)
        waits = sorted(r.t_admit - r.t_submit for r in completed
                       if r.t_admit > 0.0)

        def pct(p: float) -> float:
            # guarded on the DATA, not on the callable: zero completed
            # requests yield zeroed percentiles, never an IndexError
            if not waits:
                return 0.0
            return waits[min(int(p / 100 * len(waits)), len(waits) - 1)]

        pool_total = eng.pool_pages if eng.paged else 0
        pool_free = eng.allocator.n_free if eng.paged else 0
        hp = eng.host_pool               # None unless swap="host"
        return {
            "queue_depth": len(sched._waiting),
            "running": int(sched._active.sum()),
            "slots": eng.batch,
            "inflight": self._inflight,
            "max_pending": self.max_pending,
            "pool_pages": pool_total,
            "pool_free": pool_free,
            "pool_occupancy": (1.0 - pool_free / pool_total
                               if pool_total else 0.0),
            # host swap pool (all zeros unless EngineConfig(swap="host");
            # `is not None` because an empty HostPagePool is falsy)
            "swapped": len(hp) if hp is not None else 0,
            "host_pool_bytes": hp.capacity if hp is not None else 0,
            "host_pool_used_bytes": hp.used_bytes if hp is not None else 0,
            "host_pool_peak_bytes": hp.peak_used if hp is not None else 0,
            "host_pool_occupancy": (hp.used_bytes / hp.capacity
                                    if hp is not None and hp.capacity
                                    else 0.0),
            "finished": len(completed),
            "aborted": len(sched._finished) - len(completed),
            "preemptions": sched._n_preempt,
            "p50_wait_s": pct(50),
            "p99_wait_s": pct(99),
            "uptime_s": time.perf_counter() - sched._t_start,
        }

    # ------------------------------------------------------------------
    # dispatch loop
    # ------------------------------------------------------------------
    def _deliver(self) -> None:
        """Drain the core's emit buffer into per-request queues and send
        finish sentinels (+ release backpressure tickets) for newly
        finished/aborted requests."""
        sched = self.scheduler
        for req, toks, lps in sched._emit:
            q = self._queues.get(req.rid)
            if q is not None:
                for pair in zip(toks, lps):
                    q.put_nowait(pair)
        sched._emit.clear()
        while self._n_fin < len(sched._finished):
            req = sched._finished[self._n_fin]
            self._n_fin += 1
            if req.rid in self._done:
                continue
            self._done.add(req.rid)
            q = self._queues.pop(req.rid, None)
            if q is not None:
                q.put_nowait(None)
            self._inflight -= 1
            self._sem.release()

    def _fail(self, err: BaseException) -> None:
        """Dispatch loop died: surface the error on every open stream and
        on future submits, and unblock backpressure waiters."""
        self._error = err
        for rid, q in list(self._queues.items()):
            if rid not in self._done:
                self._done.add(rid)
                q.put_nowait(err)
                self._inflight -= 1
                self._sem.release()
        self._queues.clear()

    async def _run(self) -> None:
        """The wall-clock driver of the shared loop core: admit → grow →
        dispatch → harvest, yielding to clients between syncs, parking on
        the wake event when idle."""
        sched = self.scheduler
        try:
            while True:
                sched._advance(0.0)
                if not sched._waiting and not sched._active.any():
                    if self._closing:
                        break
                    self._wake.clear()
                    await self._wake.wait()
                    continue
                with span("serve.loop"):
                    sched._admit_waiting()
                    with span("serve.deliver"):
                        self._deliver()  # EOS-at-prefill finishes
                    if not sched._active.any():
                        if sched._waiting:
                            raise RuntimeError(
                                "no active slot and the head request cannot "
                                "be admitted — page pool leak?")
                        continue
                    run = sched._grow()
                    sched._dispatch(run)  # blocking jax compute
                    sched._harvest()
                    with span("serve.deliver"):
                        self._deliver()
                    # hand the loop to submitters/consumers between syncs —
                    # this is the only point client coroutines mutate core
                    # state (submit/abort), so the sync above sees a stable
                    # view without locks
                    with span("serve.yield"):
                        await asyncio.sleep(0)
        except BaseException as e:       # noqa: BLE001 — surfaced to clients
            self._fail(e)
        finally:
            sched._advance(0.0)
            self._report = sched._end_session(
                time.perf_counter() - sched._t_start)


def virtual_twin_report(engine: Engine, workload, eos_id: Optional[int]
                        = None, **scheduler_kwargs) -> Dict[str, Any]:
    """Run ``workload`` — a list of (prompt, SamplingParams|None,
    max_new_tokens|None) tuples — through the deterministic virtual-clock
    driver, returning its report. The reference the streaming tests and
    benchmark compare token streams against."""
    reqs = [Request(np.asarray(p, np.int32), sampling=sp,
                    max_new_tokens=mnt) for p, sp, mnt in workload]
    sched = Scheduler(engine, eos_id=eos_id, **scheduler_kwargs)
    rep = sched.serve(reqs)
    order = {r.rid: i for i, r in enumerate(reqs)}
    rep["results"] = sorted(rep["results"], key=lambda r: order[r["rid"]])
    return rep
