"""Serving runner: a traffic mix through the program's streaming front end.

The timed path is the one ``launch/serve_stream.py`` serves: an
``AsyncEngine`` (submit / stream) over the ``Scheduler`` and a paged
``Engine`` built by ``launch/build.py::build_engine``, with the engine
settings the mix names (``engine``) and every other ``EngineConfig`` field
at the program's default. Every request is greedy with no end token, so it
ends at its budget.

The engine runs without a drafter (``drafter_mode`` "none": one target
position per slot and iteration). Under greedy verification a drafter's
proposals never reach a served token, and the program does not expose
them, so nothing here could check a speculative mode's drafting; a mix
that names one is refused.

A run: weights from the seed (``bench/weights.py``), the engine, a warm-up
session that admits one request at each prompt length the mix can send
(so every prefill bucket, the decode step at the cell's slot count, and the
admit/grow/free programs are compiled or read from the cache), then the
measured session: ``ramp_s`` of closed-loop load, the window of
``--seconds``, and a wait for the first token of every request that fell
due in the window. The program's state is then freed and the sample of
finished requests is checked against the plain reference
(``bench/correct.py``).
"""
from __future__ import annotations

import asyncio
import gc
import itertools
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from bench import correct as C
from bench import flops as F
from bench import harness as H
from bench import traffic as T

FIRST_TOKEN_WAIT_S = 60.0      # past the window, for requests due in it
TRACE_DIR = "bench_out/trace"


@dataclass
class Record:
    prompt: np.ndarray
    max_new: int
    due: float
    handle: object = None
    rid: int = -1
    t_admit: float = 0.0       # the scheduler's admission stamp (0: never)
    tokens: List[int] = field(default_factory=list)
    times: List[float] = field(default_factory=list)
    finished: bool = False


@dataclass
class ServeRun:
    cell: object
    config: dict
    mix: dict
    setup_s: float
    records: List[Record]
    window: tuple
    report: dict
    device: dict
    peak: object
    weight_bytes: int
    cache_itemsize: int
    checks: list = field(default_factory=list)
    trace: Optional[object] = None
    trace_window: Optional[tuple] = None
    iter_log: list = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    control_checks: Optional[list] = None   # bench/control.py only

    # --- what the metric readers read ----------------------------------
    def due_in_window(self) -> List[Record]:
        w0, w1 = self.window
        return [r for r in self.records if w0 <= r.due < w1]

    def finished_in_window(self) -> List[Record]:
        w0, w1 = self.window
        return [r for r in self.records
                if r.finished and r.times and w0 <= r.times[-1] < w1]

    def results_by_rid(self) -> dict:
        return {res["rid"]: res for res in self.report["results"]}

    def tokens_between(self, t0: float, t1: float) -> int:
        return sum(int(np.searchsorted(r.times, t1) - np.searchsorted(r.times, t0))
                   for r in self.records)

    def prompt_tokens_admitted(self, t0: float, t1: float) -> int:
        return sum(r.prompt.size for r in self.records
                   if t0 <= r.t_admit < t1)

    def decode_bound_s(self, t0: float, t1: float) -> Optional[float]:
        """Mean over the iterations dispatched in [t0, t1) of the least
        time the chip could take for one (roofline bound)."""
        bounds = []
        for t, live in self.iter_log:
            if t0 <= t < t1 and live:
                fl, nb = F.decode_iteration(
                    self.cell.family, self.config, live, self.weight_bytes,
                    self.cache_itemsize)
                bounds.append(max(fl / self.peak.flops,
                                  nb / self.peak.hbm_bytes_s))
        return sum(bounds) / len(bounds) if bounds else None

    def useful_target_flops(self, t0: float, t1: float) -> float:
        """Target forward operations of the prompts admitted and the output
        tokens committed in [t0, t1): a prompt of P tokens is one causal
        forward with one LM-head row (its first output token); output token
        j >= 1 is one position after P + j - 1 cached ones."""
        fam, c = self.cell.family, self.config
        total = 0.0
        for r in self.records:
            P = int(r.prompt.size)
            if t0 <= r.t_admit < t1:
                total += fam.flops(c, 0, P, 1)
            lo = max(int(np.searchsorted(r.times, t0)), 1)
            hi = int(np.searchsorted(r.times, t1))
            for j in range(lo, hi):
                total += fam.flops(c, P + j - 1, 1, 1)
        return total


def build(cell, seed: int):
    """(engine, target params, bytes of all weights) for the cell."""
    import jax
    from repro.configs import get_config
    from repro.launch.build import build_engine
    from repro.models import get_model
    from repro.serving import EngineConfig

    from bench import weights

    cfg, mix = cell.config, cell.mix
    tcfg = get_config(cfg["program_arch"])
    H.check_config(cell.family, cfg, tcfg)
    e = mix["engine"]
    if e["drafter_mode"] != "none":
        raise ValueError(f"drafter_mode {e['drafter_mode']!r}: the program "
                         "does not expose its drafts, so no check could "
                         "cover them; serving cells run 'none'")
    key = jax.random.PRNGKey(H.seed32(seed))
    model = get_model(tcfg)
    tparams = weights.make(jax.eval_shape(model.init, key),
                           jax.random.fold_in(key, 1), cell.family)
    ecfg = EngineConfig(drafter_mode="none", max_len=e["max_len"],
                        kv_layout="paged", page_size=e["page_size"])
    eng = build_engine(tcfg, tparams, ecfg, e["slots"])
    nbytes = sum(x.nbytes for x in jax.tree.leaves(tparams))
    return eng, tparams, nbytes


def _instrument(sched, iter_log: list) -> None:
    """Host spans around the scheduler's loop phases, and a log of each
    dispatch's active slots with their committed lengths (traced run only;
    bench-side wrappers, the program is unchanged)."""
    from jax.profiler import TraceAnnotation

    def span(name, fn):
        def wrapped(*a, **k):
            with TraceAnnotation("bench." + name):
                return fn(*a, **k)
        return wrapped

    dispatch = sched._dispatch

    def logged_dispatch(run):
        live = [int(r.prompt.size + len(r.out_tokens))
                for s, r in enumerate(sched._slot_req)
                if r is not None and run[s]]
        iter_log.append((H.now(), live))
        return dispatch(run)

    sched._dispatch = span("dispatch", logged_dispatch)
    for name in ("_admit_waiting", "_grow", "_harvest"):
        setattr(sched, name, span(name.strip("_"), getattr(sched, name)))


async def _warm(eng, lengths: List[int], vocab: int) -> None:
    from repro.serving import SamplingParams
    from repro.serving.streaming import AsyncEngine
    aeng = AsyncEngine(eng, max_pending=len(lengths))

    async def one(n):
        prompt = (np.arange(n) % (vocab - 2)).astype(np.int32)
        h = await aeng.submit(prompt, SamplingParams.greedy(),
                              max_new_tokens=3)
        async for _ in h:
            pass

    await asyncio.gather(*(one(n) for n in lengths))
    await aeng.close()


async def _measure(eng, cell, seed: int, seconds: float, traced: bool,
                   counter, out: dict) -> None:
    import jax
    from repro.serving import SamplingParams
    from repro.serving.streaming import AsyncEngine

    mix = cell.mix
    vocab = cell.config["vocab_size"]
    # no admission tickets to wait for: a request that cannot be admitted
    # waits in the scheduler's queue, where the window sees it
    aeng = AsyncEngine(eng, max_pending=1 << 30)
    iter_log: list = []
    if traced:
        _instrument(aeng.scheduler, iter_log)
    await aeng.start()
    records: List[Record] = []
    stop = asyncio.Event()
    greedy = SamplingParams.greedy()

    async def serve_one(rec: Record):
        rec.handle = await aeng.submit(rec.prompt, greedy,
                                       max_new_tokens=rec.max_new)
        rec.rid = rec.handle.rid
        records.append(rec)
        async for tok, _ in rec.handle:
            rec.times.append(H.now())
            rec.tokens.append(int(tok))
        rec.finished = not rec.handle.aborted

    n = mix["clients"] * mix["cycle"]
    reqs = T.plan(mix, seed, vocab, n)

    async def client(i):
        mine = [r for r in reqs if r.client == i]
        for j, r in enumerate(itertools.cycle(mine)):
            if stop.is_set():
                return
            mx = T.stagger(mix, i, r.max_new) if j == 0 else r.max_new
            await serve_one(Record(r.prompt, mx, H.now()))

    t_load = H.now()
    tasks = [asyncio.ensure_future(client(i)) for i in range(mix["clients"])]
    await asyncio.sleep(max(0.0, t_load + mix["ramp_s"] - H.now()))
    w0 = H.now()
    counter.mark()
    trace_window = None
    if traced:
        await asyncio.sleep(mix["trace_offset_s"])
        jax.profiler.start_trace(str(H.ROOT / TRACE_DIR))
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
        ts0 = H.now()
        await asyncio.sleep(mix["trace_s"])
        ts1 = H.now()
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        trace_window = (ts0, ts1)
    await asyncio.sleep(max(0.0, w0 + seconds - H.now()))
    w1 = H.now()
    compiles = counter.since_mark()
    stop.set()
    deadline = w1 + FIRST_TOKEN_WAIT_S
    while H.now() < deadline and any(
            w0 <= r.due < w1 and not r.times and not r.handle.done
            for r in records):
        await asyncio.sleep(0.005)
    for r in records:
        if not r.handle.done:
            r.handle.abort()
    await asyncio.gather(*tasks)
    for r in records:            # handles hold the session and its state
        r.t_admit = r.handle.request.t_admit
        r.handle = None
    out.update(records=records, window=(w0, w1), compiles=compiles,
               report=await aeng.close(drain=False), iter_log=iter_log,
               trace_window=trace_window)


def run(cell, seed: int, seconds: float, traced: bool, t_start: float,
        counter, devices, control: bool = False) -> ServeRun:
    """One run of a serving cell. ``control`` (``bench/control.py`` only,
    never a benchmark run) also reads the float8 control's widest gap on
    the same sample and keeps the checks it makes in
    ``ServeRun.control_checks``."""
    from bench.peaks import peak_for

    peak = peak_for(devices[0].device_kind)
    eng, tparams, wbytes = build(cell, seed)
    vocab = cell.config["vocab_size"]
    asyncio.run(_warm(eng, T.prompt_lengths(cell.mix), vocab))
    setup_s = H.now() - t_start
    H.log(f"set-up {setup_s:.3f} s ({counter.count} programs lowered)")
    if traced:
        import shutil
        shutil.rmtree(H.ROOT / TRACE_DIR, ignore_errors=True)
    out: dict = {}
    asyncio.run(_measure(eng, cell, seed, seconds, traced, counter, out))
    H.log(f"programs lowered inside the window: {out['compiles']}")
    device = H.device_info(devices, cell.chips)
    # the engine allocates its caches (and an SSM's conv window) in this
    # dtype; the SSM state itself is float32
    cache_itemsize = F.DTYPE_BYTES[eng.ecfg.cache_dtype]
    del eng
    gc.collect()

    r = ServeRun(cell=cell, config=cell.config, mix=cell.mix, setup_s=setup_s,
                 records=out["records"], window=out["window"],
                 report=out["report"], device=device, peak=peak,
                 weight_bytes=wbytes, cache_itemsize=cache_itemsize,
                 iter_log=out["iter_log"],
                 trace_window=out["trace_window"])
    due = r.due_in_window()
    r.attempted = len(due)
    r.failed = sum(1 for x in due if not x.times)
    if traced:
        from bench import trace as TR
        events = TR.events_from_xplane(TR.find_xplane(str(H.ROOT / TRACE_DIR)))
        r.trace = TR.reduce(events, window_span="bench.window")
        H.log("traced programs (device s, calls): " + ", ".join(
            f"{k} {v[0]:.4f} {v[1]}" for k, v in sorted(
                r.trace.programs.items(), key=lambda kv: -kv[1][0])[:8]))
    r.checks = check(r, tparams, seed, control)
    return r


def check(r: ServeRun, tparams, seed: int, control: bool = False) -> list:
    """The checks of a served run: every finished request's stream equals
    what the engine committed and ends at its budget, and the sample's
    widest gap below the plain reference's best logit. With ``control`` the
    same checks with the control's widest gap go to ``r.control_checks``."""
    res = r.results_by_rid()
    finished = [x for x in r.records if x.finished]
    mismatch = sum(1 for x in finished
                   if x.tokens != res[x.rid]["tokens"].tolist())
    budget = sum(1 for x in finished if len(x.tokens) != x.max_new)
    limits = r.cell.check["limits"]
    checks = [H.Check("stream_mismatch", mismatch, limits["stream_mismatch"]),
              H.Check("budget_errors", budget, limits["budget_errors"])]
    pairs = [(x.prompt, np.asarray(x.tokens, np.int32)) for x in finished]
    pick = C.choose_sample(pairs, r.cell.check["sample_tokens"],
                           H.seed32(seed, 3))
    if not pick:
        H.log("no finished request to compare")
        checks.append(H.Check("widest_gap", float("inf"),
                              limits["widest_gap"]))
        r.control_checks = list(checks) if control else None
        return checks
    ref = r.cell.reference(r.config["reference"])
    t0 = H.now()
    gaps = C.served_gaps(ref, r.config, tparams, [pairs[i] for i in pick],
                         r.mix["engine"]["max_len"])
    n = sum(len(g) for g in gaps)
    H.log(f"reference over {len(pick)} requests, {n} served tokens, "
          f"in {H.now() - t0:.3f} s")
    if control:
        cg = C.served_gaps(ref, r.config, tparams, [pairs[i] for i in pick],
                           r.mix["engine"]["max_len"], control=True)
        r.control_checks = checks + [H.Check(
            "widest_gap", max(float(g.max()) for g in cg),
            limits["widest_gap"])]
    return checks + [H.Check("widest_gap", max(float(g.max()) for g in gaps),
                             limits["widest_gap"])]
