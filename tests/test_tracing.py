"""The program's own measurement: stable names for its jitted programs,
named scopes inside them, host spans on the profiler's clock, and the
draft log kept in the decode state.

- the decode step and the block-table row setter lower to modules named
  after their functions, and their ops carry the scopes the benchmark's
  per-layer metrics read (``gather``/``scatter``/``draft``/``verify`` in the
  paged step, ``taps``/``drafter``/``update`` in the train step);
- a tiny streamed session and a trainer step under ``jax.profiler.trace``
  write every ``serve.*`` / ``train.*`` span, and collections show as
  ``host.gc`` spans and in the report's counts;
- with a drafter, row c of a finished request's draft log holds the K
  drafts proposed from position c: greedy verification accepted exactly
  its matching prefix, on both KV layouts alike; without one there is no
  such leaf.
"""
import asyncio
import gc
import glob
import re
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import DrafterConfig, get_config
from repro.core import drafter as D
from repro.data import MTPPipeline, markov_corpus
from repro.models import get_model
from repro.serving import (AsyncEngine, Engine, EngineConfig, Request,
                           SamplingParams, Scheduler)
from repro.training import TrainConfig, Trainer
from repro.training.trainer import make_train_step

KEY = jax.random.PRNGKey(5)
K = 3


@lru_cache(maxsize=None)
def _setup(zero: bool = False):
    tcfg = get_config("qwen2-1.5b").reduced()
    tparams = get_model(tcfg).init(KEY)
    dcfg = DrafterConfig(n_layers=1, k_infer=K).resolve(tcfg)
    dparams = D.init_params(dcfg, tcfg, jax.random.fold_in(KEY, 1))
    if zero:
        # every logit 0: target and drafter both pick token 0 everywhere,
        # so every draft is accepted
        tparams, dparams = (jax.tree.map(jnp.zeros_like, t)
                            for t in (tparams, dparams))
    return tcfg, dcfg, tparams, dparams


@lru_cache(maxsize=None)
def engine(kv_layout="paged", mode="parallel", zero=False):
    tcfg, dcfg, tparams, dparams = _setup(zero)
    if mode == "none":
        dcfg = dparams = None
    return Engine(tcfg, dcfg, tparams, dparams,
                  EngineConfig(K=K if mode != "none" else 0,
                               max_new_tokens=16, drafter_mode=mode,
                               max_len=64, kv_layout=kv_layout,
                               page_size=8), 2)


def prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 200, size=int(rng.integers(3, 9))
                         ).astype(np.int32) for _ in range(n)]


def op_names(compiled_text: str) -> set:
    return set(re.findall(r'op_name="([^"]+)"', compiled_text))


def scopes(names: set, program: str) -> set:
    """Words of the path components of the op names under
    ``jit(program)``, seen through ``jit(...)`` and other wrappers (the last
    component is the primitive, not a scope)."""
    out = set()
    for n in names:
        parts = n.split("/")
        if parts[0] == f"jit({program})":
            for c in parts[1:-1]:
                out.update(re.findall(r"[^()]+", c))
    return out


# ---------------------------------------------------------------------------
# stable names and scopes
# ---------------------------------------------------------------------------

def test_decode_step_and_row_setter_lower_to_named_modules():
    eng = engine()
    state = eng.blank_state()
    B = eng.batch
    args = (eng.tparams, eng.dparams, *eng.split_pools(state),
            jnp.ones((B,), bool), jnp.full((B,), 8, jnp.int32),
            jnp.full((B,), K, jnp.int32))
    for greedy in (False, True):
        lowered = eng._paged_step[greedy].lower(*args)
        assert lowered.as_text().startswith("module @jit__paged_step_impl")
    compiled = eng._paged_step[True].lower(*args).compile().as_text()
    found = scopes(op_names(compiled), "_paged_step_impl")
    assert {"gather", "scatter", "draft", "verify", "accept",
            "commit"} <= found
    row = eng._set_table_row.lower(
        state["block_table"], jnp.asarray(0, jnp.int32),
        jnp.full((eng.pages_per_slot,), -1, jnp.int32))
    assert row.as_text().startswith("module @jit__set_table_row_impl")


def test_train_step_ops_carry_their_scopes():
    tcfg, _, tparams, _ = _setup()
    dcfg = DrafterConfig(n_layers=1, k_train=2).resolve(tcfg)
    corpus = markov_corpus(0, 4, 16, tcfg.vocab_size)
    b = next(iter(MTPPipeline(corpus, k_train=2, cod_rate=0.8, batch=2)))
    dparams = D.init_params(dcfg, tcfg, KEY)
    from repro.optim import adamw_init
    step = make_train_step(tcfg, dcfg, TrainConfig())
    lowered = step.lower(tparams, dparams, adamw_init(dparams),
                         *(jnp.asarray(x) for x in (b.tokens, b.pos, b.depth,
                                                    b.labels)), KEY)
    text = lowered.as_text()
    assert text.startswith("module @jit_step")
    # the layers are named sub-programs of the step's structure, so the
    # compile cache, which ignores op_name metadata, keys them
    for layer in ("taps", "drafter", "update"):
        assert f"func.func private @{layer}(" in text
    found = scopes(op_names(lowered.compile().as_text()), "step")
    assert {"taps", "drafter", "update"} <= found


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

def host_span_names(trace_dir) -> list:
    from jax.profiler import ProfileData
    names = []
    for f in glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True):
        for plane in ProfileData.from_file(f).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                names += [e.name for e in line.events]
    return names


SERVE_SPANS = {"serve.loop", "serve.admit", "serve.prefill", "serve.grow",
               "serve.blank", "serve.dispatch", "serve.readback",
               "serve.harvest", "serve.deliver", "serve.yield",
               "serve.request.arrive", "serve.request.admit",
               "serve.request.finish", "host.gc"}
TRAIN_SPANS = {"train.batch", "train.put", "train.step", "train.readback",
               "host.gc"}


def test_streamed_session_writes_every_serve_span(tmp_path):
    eng = engine()
    # warm the programs outside the trace: spans, not compiles, are under
    # test
    Scheduler(eng).serve([Request(p, max_new_tokens=10)
                          for p in prompts(2, seed=1)])

    async def session():
        aeng = AsyncEngine(eng)
        hs = [await aeng.submit(p, SamplingParams.greedy(),
                                max_new_tokens=10) for p in prompts(3)]
        for h in hs:
            async for _ in h:
                gc.collect()
        return aeng, await aeng.close()

    with jax.profiler.trace(str(tmp_path)):
        aeng, report = asyncio.run(session())
    names = set(host_span_names(tmp_path))
    assert SERVE_SPANS <= names, SERVE_SPANS - names
    assert report["gc_collections"] >= 1 and report["gc_s"] > 0
    # the session's hook went with the session
    assert aeng.scheduler._gc._on_gc not in gc.callbacks


def test_trainer_step_writes_every_train_span(tmp_path):
    tcfg, _, tparams, _ = _setup()
    dcfg = DrafterConfig(n_layers=1, k_train=2).resolve(tcfg)
    corpus = markov_corpus(0, 8, 16, tcfg.vocab_size)
    pipe = MTPPipeline(corpus, k_train=2, cod_rate=0.8, batch=2)
    tr = Trainer(tcfg, dcfg, tparams, TrainConfig())
    feed = iter(pipe)
    tr.train_batch(next(feed))        # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        m = tr.train_batch(next(feed))
        gc.collect()
    assert np.isfinite(m["loss"])
    names = set(host_span_names(tmp_path))
    assert TRAIN_SPANS <= names, TRAIN_SPANS - names
    hook = tr.gc._on_gc
    assert hook in gc.callbacks
    del tr
    gc.collect()
    assert hook not in gc.callbacks   # the hook goes with the trainer


# ---------------------------------------------------------------------------
# the draft log
# ---------------------------------------------------------------------------

def served(eng, ps, budget=12):
    rep = Scheduler(eng).serve([Request(p, max_new_tokens=budget)
                                for p in ps])
    return [(p, r) for p, r in zip(ps, rep["results"])]


def check_accept_prefix(prompt, res):
    """Each iteration's drafts against what greedy verification kept."""
    stream = np.concatenate([prompt, res["tokens"]])
    drafts = res["drafts"]
    assert drafts.shape == (stream.size, K)
    rows = np.flatnonzero((drafts >= 0).any(axis=1))
    assert rows.size == res["iters"] and rows[0] == prompt.size
    accepted = []
    # an iteration from c that accepts a drafts commits a + 1 tokens, so
    # the next one proposes from c + a + 1 (the last one's commit may be
    # cut at the budget, so it is left out)
    for c, nxt in zip(rows[:-1], rows[1:]):
        a = int(nxt - c - 1)
        assert 0 <= a <= K
        np.testing.assert_array_equal(drafts[c, :a], stream[c + 1:c + 1 + a])
        if a < K:
            assert drafts[c, a] != stream[c + 1 + a]
        accepted.append(a)
    return accepted


@pytest.mark.parametrize("zero", [False, True], ids=["seeded", "accepting"])
def test_draft_log_holds_each_iterations_proposal(zero):
    accepted = []
    for p, res in served(engine(zero=zero), prompts(3)):
        accepted += check_accept_prefix(p, res)
    if zero:
        assert max(accepted) == K        # every draft accepted
    else:
        assert min(accepted) == 0        # the seeded drafter misses


def test_draft_log_is_equal_across_kv_layouts():
    ps = prompts(3, seed=2)
    paged = served(engine("paged"), ps)
    contiguous = served(engine("contiguous"), ps)
    for (_, a), (_, b) in zip(paged, contiguous):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        np.testing.assert_array_equal(a["drafts"], b["drafts"])


def test_no_draft_leaf_without_a_drafter():
    eng = engine(mode="none")
    assert "drafts" not in eng.blank_state()
    (_, res), = served(eng, prompts(1))
    assert "drafts" not in res and res["n_new"] == 12
