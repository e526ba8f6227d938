"""Per-request SamplingParams API: validation, logit warping, deterministic
PRNG streams, and the mixed-policy serving invariants.

The acceptance pins of the SamplingParams redesign:

- **per-request determinism**: a seeded sampled request's tokens are a pure
  function of ``(seed, prompt)`` — bitwise identical across runs, batch
  compositions, slot indices, KV layouts, and mesh sizes;
- **mixed-policy batches**: greedy and sampled requests share one jitted
  step per layout, and the greedy rows emit exactly what a pure-greedy
  engine emits (the pre-redesign output);
- **deprecation**: ``EngineConfig(greedy=...)`` still works but emits
  exactly one DeprecationWarning;
- warp correctness (temperature / top-k / top-p) and the spec_decode
  robustness fixes (zero-active stats guard, explicit residual
  renormalization) are unit-tested directly.
"""
import warnings
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import DrafterConfig, get_config
from repro.core import drafter as D
from repro.core import spec_decode as SD
from repro.models import get_model
from repro.serving import (Engine, EngineConfig, LLMEngine, Request,
                           SamplingParams, Scheduler)
from repro.sharding.utils import serving_mesh

from conftest import require_devices  # noqa: E402  (tests dir on sys.path)

KEY = jax.random.PRNGKey(23)


# ---------------------------------------------------------------------------
# SamplingParams validation + EngineConfig deprecation
# ---------------------------------------------------------------------------

def test_sampling_params_validation():
    SamplingParams(temperature=0.7, top_k=5, top_p=0.9, seed=3,
                   stop_token_ids=(7,), max_new_tokens=4)   # all fine
    assert SamplingParams.greedy().is_greedy
    assert not SamplingParams(temperature=0.1).is_greedy
    for bad in [dict(temperature=-0.1), dict(temperature=float("inf")),
                dict(top_k=-1), dict(top_p=0.0), dict(top_p=1.5),
                dict(seed=1.5), dict(max_new_tokens=0)]:
        with pytest.raises(ValueError):
            SamplingParams(**bad)


def test_engine_config_greedy_deprecated_exactly_once():
    """The alias still constructs a working default SamplingParams but warns
    exactly once per construction."""
    for flag, want_greedy in [(True, True), (False, False)]:
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            cfg = EngineConfig(greedy=flag)
        dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
        assert len(dep) == 1, f"greedy={flag}: {len(dep)} warnings"
        assert cfg.sampling.is_greedy == want_greedy
    # the replacement spelling is silent
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        cfg = EngineConfig(sampling=SamplingParams(temperature=0.5, seed=9))
        EngineConfig()
    assert not [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert cfg.sampling.temperature == 0.5


# ---------------------------------------------------------------------------
# warp + spec_decode units
# ---------------------------------------------------------------------------

def _warp1(logits, **kw):
    sp = dict(temperature=1.0, top_k=0, top_p=1.0)
    sp.update(kw)
    return np.asarray(SD.warp_probs(
        jnp.asarray(logits, jnp.float32)[None, None, :],
        jnp.full((1,), sp["temperature"], jnp.float32),
        jnp.full((1,), sp["top_k"], jnp.int32),
        jnp.full((1,), sp["top_p"], jnp.float32)))[0, 0]


def test_warp_temperature_scales_logits():
    logits = [0.0, 1.0, 2.0, -1.0]
    for t in (0.5, 1.0, 2.0):
        want = np.asarray(jax.nn.softmax(jnp.asarray(logits) / t))
        np.testing.assert_allclose(_warp1(logits, temperature=t), want,
                                   rtol=1e-6)


def test_warp_top_k_masks_and_renormalizes():
    p = _warp1([3.0, 2.0, 1.0, 0.0], top_k=2)
    assert p[2] == 0.0 and p[3] == 0.0
    np.testing.assert_allclose(p.sum(), 1.0, rtol=1e-6)
    want = np.asarray(jax.nn.softmax(jnp.asarray([3.0, 2.0])))
    np.testing.assert_allclose(p[:2], want, rtol=1e-6)


def test_warp_top_p_keeps_minimal_nucleus():
    # probs ~ [0.643, 0.237, 0.087, 0.032]: top_p=0.8 keeps the first two
    p = _warp1([3.0, 2.0, 1.0, 0.0], top_p=0.8)
    assert p[2] == 0.0 and p[3] == 0.0 and p[0] > p[1] > 0
    np.testing.assert_allclose(p.sum(), 1.0, rtol=1e-6)
    # top-1 always kept even under a degenerate top_p from a blank slot
    p = _warp1([3.0, 2.0, 1.0, 0.0], top_p=1e-9)
    assert np.isfinite(p).all() and p[0] == 1.0


def test_sample_token_greedy_rows_are_argmax():
    logits = jax.random.normal(KEY, (4, 16))
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    t = jnp.asarray([0.0, 0.0, 1.0, 1.0])
    tok = SD.sample_token(keys, logits, t, jnp.zeros(4, jnp.int32),
                          jnp.ones(4))
    np.testing.assert_array_equal(np.asarray(tok[:2]),
                                  np.asarray(jnp.argmax(logits[:2], -1)))
    # sampled rows: deterministic per key
    tok2 = SD.sample_token(keys, logits, t, jnp.zeros(4, jnp.int32),
                           jnp.ones(4))
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok2))


def test_acceptance_stats_zero_active_guard():
    s = SD.update_acceptance_stats({}, jnp.array([2, 3]),
                                   active=jnp.array([False, False]))
    assert int(s["iters"]) == 0 and int(s["tokens"]) == 0
    assert np.isfinite(float(s["mean"]))          # no 0/0 NaN
    s = SD.update_acceptance_stats(s, jnp.array([2, 3]),
                                   active=jnp.array([True, False]))
    assert (int(s["iters"]), int(s["tokens"])) == (1, 3)
    assert float(s["mean"]) == 3.0
    assert SD.acceptance_length(s) == 3.0


def test_rejection_residual_renormalization_exact():
    """Deterministic rejection: q is a delta on token 0, p a delta on token
    1 — the draft is always rejected and the residual norm(max(p-q, 0)) is a
    delta on token 1, with no epsilon fudge leaking probability elsewhere."""
    V = 6
    q = jnp.zeros((1, 1, V)).at[0, 0, 0].set(1.0)
    p = jnp.zeros((1, 2, V)).at[:, :, 1].set(1.0)
    for s in range(5):
        acc, committed = SD.rejection_verify(
            jax.random.PRNGKey(s), jnp.zeros((1, 1), jnp.int32), q, p)
        assert int(acc[0]) == 0
        assert int(committed[0, 0]) == 1          # exactly the residual token
    # p == q exactly: the residual is all-zero; the guarded renormalization
    # falls back to the target row instead of emitting NaN
    acc, committed = SD.rejection_verify(
        KEY, jnp.zeros((1, 1), jnp.int32), p[:, :1], p)
    assert np.isfinite(np.asarray(committed)).all()
    assert int(committed[0, 0]) == 1


def test_deterministic_draft_one_hot_proposal_is_lossless():
    """The engine's drafts are argmax — a deterministic proposal — so it
    verifies them against a ONE-HOT draft distribution: accept w.p. p(d),
    residual norm(p masked at d). The committed token's empirical
    distribution must then match the target p exactly, whatever token the
    drafter proposed. (Using the drafter softmax as q here would
    over-accept the drafter's argmax — the bias this test guards against.)"""
    V, N = 8, 30_000
    key = jax.random.PRNGKey(3)
    p = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (V,)))
    d = int(jnp.argmax(p))                        # worst case: most-likely
    q = jax.nn.one_hot(jnp.asarray([d]), V)[None]

    def one(k):
        _, committed = SD.rejection_verify(
            k, jnp.asarray([[d]], jnp.int32), q, jnp.stack([p, p])[None])
        return committed[0, 0]

    toks = jax.vmap(one)(jax.random.split(key, N))
    emp = np.bincount(np.asarray(toks), minlength=V) / N
    np.testing.assert_allclose(emp, np.asarray(p), atol=0.015)


# ---------------------------------------------------------------------------
# serving invariants (determinism, mixed policy, layouts, mesh)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _setup():
    tcfg = get_config("qwen2-1.5b").reduced()
    m = get_model(tcfg)
    tparams = m.init(KEY)
    dcfg = DrafterConfig(n_layers=1, k_infer=2).resolve(tcfg)
    dparams = D.init_params(dcfg, tcfg, jax.random.fold_in(KEY, 1))
    return tcfg, dcfg, tparams, dparams


@lru_cache(maxsize=None)
def get_engine(kv_layout="contiguous", batch=2, shard=0, bucket=True,
               mode="parallel", prefix_cache=False, swap=False):
    tcfg, dcfg, tparams, dparams = _setup()
    return Engine(tcfg, dcfg, tparams, dparams,
                  EngineConfig(K=2 if mode != "none" else 0,
                               max_new_tokens=8,
                               drafter_mode=mode, max_len=64,
                               kv_layout=kv_layout, page_size=8,
                               bucket_prefill=bucket,
                               prefix_cache=prefix_cache,
                               swap="host" if swap else "none",
                               pool_pages=5 if swap else 0,
                               shard_model=shard > 0,
                               mesh=serving_mesh(shard) if shard else None),
                  batch)


def _prompts(n, seed=0, lo=4, hi=10):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 200, size=int(rng.integers(lo, hi))
                         ).astype(np.int32) for _ in range(n)]


SAMPLED = SamplingParams(temperature=0.8, top_k=40, top_p=0.95, seed=1234)


def test_same_seed_same_tokens_regardless_of_batch_composition():
    """The determinism acceptance pin: one seeded request's tokens are
    identical whether it runs alone, first, last, or among different
    neighbors — per-row keys make the stream independent of everything but
    (seed, prompt)."""
    eng = get_engine()
    target = _prompts(1, seed=3)[0]
    others = _prompts(4, seed=4)
    solo = Scheduler(eng).serve(
        [Request(target, sampling=SAMPLED)])["results"][0]["tokens"]
    for order in ([target] + others, others + [target],
                  others[:2] + [target] + others[2:]):
        reqs = [Request(p, sampling=SAMPLED if p is target else None)
                for p in order]
        rep = Scheduler(eng).serve(reqs)
        got = [r for q, r in zip(sorted(reqs, key=lambda r: r.rid),
                                 rep["results"]) if q.sampling == SAMPLED]
        assert len(got) == 1
        np.testing.assert_array_equal(
            got[0]["tokens"], solo,
            err_msg="seeded stream changed with batch composition")


@pytest.mark.parametrize("shard,case", [
    pytest.param(0, "parallel", id="0"),
    pytest.param(4, "parallel", id="4"),
    pytest.param(8, "parallel", id="8"),
    pytest.param(0, "none", id="0-none"),
    pytest.param(0, "ar", id="0-ar"),
    pytest.param(0, "prefix", id="0-prefix"),
    pytest.param(0, "swap", id="0-swap"),
])
def test_mixed_policy_cross_layout_losslessness(shard, case):
    """A batch mixing greedy and seeded sampled requests: paged + bucketed
    (and optionally model-sharded over ``shard`` forced host devices)
    equals the contiguous exact-length single-device engine bitwise — for
    BOTH policies. One jitted step per layout serves the whole mix. The
    single-device paged step reads the target's pools in place; its cases
    run with no drafter, the "ar" drafter, prefix-cache hits (a second
    serve on warm pages) and a swap-out/swap-in after a preemption."""
    if shard:
        require_devices(shard)
    mode = case if case in ("none", "ar") else "parallel"
    prompts = _prompts(5, seed=7, lo=3, hi=10)
    budgets = [6] * 5
    if case == "prefix":
        pre = _prompts(1, seed=8, lo=19, hi=20)[0]
        prompts = [np.concatenate([pre, p]) for p in prompts]
    if case == "swap":
        budgets = [14, 14, 8, 6, 6]
    sps = [SamplingParams.greedy(),
           SamplingParams(temperature=0.7, seed=1),
           SamplingParams(temperature=1.0, top_p=0.9, seed=2),
           None,                                  # engine default (greedy)
           SamplingParams(temperature=0.5, top_k=25, seed=3)]
    reqs = lambda: [Request(p, max_new_tokens=b, sampling=sp)  # noqa: E731
                    for p, b, sp in zip(prompts, budgets, sps)]
    ref = Scheduler(get_engine(bucket=False, mode=mode)).serve(reqs())
    eng = get_engine("paged", shard=shard, mode=mode,
                     prefix_cache=case == "prefix", swap=case == "swap")
    for _ in range(2 if case == "prefix" else 1):
        got = Scheduler(eng).serve(reqs())
        for r, g in zip(ref["results"], got["results"]):
            np.testing.assert_array_equal(
                r["tokens"], g["tokens"],
                err_msg=f"rid {r['rid']} diverged across layouts "
                        f"(shard={shard}, {case})")
    if case == "prefix":
        assert got["cache_hit_tokens"] > 0, "the workload was meant to hit"
        eng.prefix_cache.flush(eng.allocator)
    if case == "swap":
        assert got["preempt_swap"] >= 1, "the workload was meant to swap"
    assert eng.allocator.n_free == eng.pool_pages


def test_mixed_batch_greedy_rows_match_pure_greedy_engine():
    """Greedy rows of a mixed batch must emit exactly what the engine
    emitted before the redesign — pinned by comparing against an engine
    whose every request is default-greedy (itself pinned lossless vs
    vanilla AR by tests/test_serving.py)."""
    eng = get_engine()
    prompts = _prompts(4, seed=11)
    all_greedy = Scheduler(eng).serve(
        [Request(p, max_new_tokens=7) for p in prompts])
    sps = [None, SamplingParams(temperature=1.0, seed=5), None,
           SamplingParams(temperature=0.8, seed=6)]
    mixed = Scheduler(eng).serve(
        [Request(p, max_new_tokens=7, sampling=sp)
         for p, sp in zip(prompts, sps)])
    for i in (0, 2):                              # the greedy rows
        np.testing.assert_array_equal(
            mixed["results"][i]["tokens"], all_greedy["results"][i]["tokens"],
            err_msg="greedy row perturbed by sampled neighbors")
    for i in (1, 3):                              # sampled rows differ
        assert not np.array_equal(mixed["results"][i]["tokens"],
                                  all_greedy["results"][i]["tokens"])


def test_sampled_rows_reproducible_across_runs_and_seeds_distinct():
    eng = get_engine()
    p = _prompts(1, seed=13)[0]
    runs = [Scheduler(eng).serve(
        [Request(p, sampling=SamplingParams(temperature=0.9, seed=s))]
        )["results"][0]["tokens"] for s in (42, 42, 43)]
    np.testing.assert_array_equal(runs[0], runs[1])
    assert not np.array_equal(runs[0], runs[2])


def test_sampling_max_new_tokens_and_stop_ids():
    """Budget precedence (SamplingParams.max_new_tokens) and per-request
    stop_token_ids trimming (vLLM semantics: stop token included)."""
    eng = get_engine()
    p = _prompts(1, seed=17)[0]
    sp = SamplingParams(temperature=0.0, max_new_tokens=5)
    rep = Scheduler(eng).serve([Request(p, sampling=sp)])
    assert rep["results"][0]["n_new"] == 5
    full = rep["results"][0]["tokens"].tolist()
    stop = full[2]
    rep2 = Scheduler(eng).serve([Request(p, sampling=SamplingParams(
        temperature=0.0, max_new_tokens=5, stop_token_ids=(stop,)))])
    assert rep2["results"][0]["tokens"].tolist() == full[:3]
    assert rep2["results"][0]["tokens"][-1] == stop


def test_llm_engine_generate_front_end():
    """vLLM-style LLMEngine.generate: outputs in prompt order, per-prompt
    SamplingParams (broadcast or list), mixed batch in one call."""
    eng = get_engine()
    prompts = _prompts(3, seed=19)
    llm = LLMEngine(eng)
    outs = llm.generate(prompts, SamplingParams(temperature=0.8, seed=2,
                                                max_new_tokens=4))
    assert len(outs) == 3 and all(o["n_new"] == 4 for o in outs)
    # per-prompt list, mixed policies; order preserved under re-submission
    sps = [None, SamplingParams(temperature=0.8, seed=2), None]
    a = llm.generate(prompts, sps)
    b = llm.generate(list(reversed(prompts)), list(reversed(sps)))
    for x, y in zip(a, reversed(b)):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
    assert llm.last_report is not None and llm.last_report["n_requests"] == 3
    with pytest.raises(ValueError, match="sampling_params"):
        llm.generate(prompts, [None])
