"""cache_ops invariants: per-slot surgery roundtrips across every registered
model family, page-pool allocator hygiene, and the bucketed-prefill retrace
bound.

The slot-surgery properties are the correctness backbone of mid-stream
admission (scheduler → engine → cache_ops): writing a batch-1 state into
slot j then reading it back must be the identity, and every other slot must
be bit-identical — for stacked super-block KV, ring buffers, recurrent
snapshots, paged pools, and drafter caches alike, since ``batch_axes``
infers the layout structurally.
"""
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import DrafterConfig, get_config
from repro.core import drafter as D
from repro.models import get_model, make_extras
from repro.models import layers as L
from repro.serving import Engine, EngineConfig, Request, Scheduler, cache_ops

KEY = jax.random.PRNGKey(3)

# one representative reduced arch per registered family
FAMILY_ARCHS = {
    "dense": "qwen2-1.5b",
    "moe": "dbrx-132b",
    "ssm": "mamba2-780m",
    "hybrid": "recurrentgemma-2b",
    "vlm": "internvl2-1b",
    "encdec": "whisper-base",
}
BATCH = 3


@lru_cache(maxsize=None)
def _setup(family: str):
    tcfg = get_config(FAMILY_ARCHS[family]).reduced()
    m = get_model(tcfg)
    tparams = m.init(KEY)
    dcfg = DrafterConfig(n_layers=1, k_infer=2).resolve(tcfg)
    dparams = D.init_params(dcfg, tcfg, jax.random.fold_in(KEY, 1))
    return tcfg, dcfg, tparams, dparams


def fresh_engine(family: str, **ecfg_kw):
    """Uncached engine (fresh jit caches — the retrace tests count them)."""
    tcfg, dcfg, tparams, dparams = _setup(family)
    kw = dict(K=2, max_new_tokens=8, drafter_mode="parallel", max_len=64,
              page_size=8)
    kw.update(ecfg_kw)
    return Engine(tcfg, dcfg, tparams, dparams, EngineConfig(**kw), BATCH)


@lru_cache(maxsize=None)
def get_engine(family: str, kv_layout: str = "contiguous"):
    return fresh_engine(family, kv_layout=kv_layout)


def _prefill_src(eng, seed: int):
    tcfg = eng.tcfg
    prompt = jax.random.randint(jax.random.fold_in(KEY, seed), (1, 4), 1,
                                tcfg.vocab_size - 2)
    extras = (make_extras(tcfg, 1, "prefill", KEY)
              if tcfg.family in ("vlm", "encdec") else {})
    return eng.prefill(prompt, extras)


def _rows(tree, axes, slot: int):
    """Slice batch row ``slot`` out of every batched leaf."""
    return jax.tree.map(
        lambda leaf, ax: leaf if ax < 0
        else jax.lax.index_in_dim(leaf, slot, axis=ax, keepdims=True),
        tree, axes)


def _assert_trees_equal(a, b, msg):
    def chk(path, x, y):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{msg} at {jax.tree_util.keystr(path)}")
    jax.tree_util.tree_map_with_path(chk, a, b)


# ---------------------------------------------------------------------------
# write_slot / reset_slot roundtrip properties (every family)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", sorted(FAMILY_ARCHS))
@settings(max_examples=3, deadline=None)
@given(slot=st.integers(0, BATCH - 1), seed=st.integers(0, 2**31 - 1))
def test_write_slot_roundtrip_identity(family, slot, seed):
    """write(src → slot j) then read(slot j) == src row 0, bit-exact."""
    eng = get_engine(family)
    axes = eng.slot_axes
    blank = eng.blank_state()
    src = _prefill_src(eng, seed)
    out = cache_ops.write_slot(blank, src, jnp.asarray(slot, jnp.int32), axes)
    _assert_trees_equal(_rows(out, axes, slot), _rows(src, axes, 0),
                        f"{family}: slot {slot} readback != src")


@pytest.mark.parametrize("family", sorted(FAMILY_ARCHS))
@settings(max_examples=3, deadline=None)
@given(slot=st.integers(0, BATCH - 1), seed=st.integers(0, 2**31 - 1))
def test_write_slot_neighbors_untouched(family, slot, seed):
    eng = get_engine(family)
    axes = eng.slot_axes
    blank = eng.blank_state()
    src = _prefill_src(eng, seed)
    out = cache_ops.write_slot(blank, src, jnp.asarray(slot, jnp.int32), axes)
    for other in range(BATCH):
        if other == slot:
            continue
        _assert_trees_equal(_rows(out, axes, other), _rows(blank, axes, other),
                            f"{family}: neighbor slot {other} perturbed")


@pytest.mark.parametrize("family", sorted(FAMILY_ARCHS))
@settings(max_examples=3, deadline=None)
@given(slot=st.integers(0, BATCH - 1), seed=st.integers(0, 2**31 - 1))
def test_reset_slot_restores_blank(family, slot, seed):
    """write then reset returns the slot (and the whole state) to blank."""
    eng = get_engine(family)
    axes = eng.slot_axes
    blank = eng.blank_state()
    src = _prefill_src(eng, seed)
    out = cache_ops.write_slot(blank, src, jnp.asarray(slot, jnp.int32), axes)
    out = cache_ops.reset_slot(out, jnp.asarray(slot, jnp.int32), axes,
                               fills={"new_count": eng.ecfg.max_new_tokens})
    for s in range(BATCH):
        _assert_trees_equal(_rows(out, axes, s), _rows(blank, axes, s),
                            f"{family}: slot {s} not blank after reset")


def _scrub_invalid_kv(tree):
    """Zero K/V entries whose position slot is empty (-1): unallocated page
    regions gather arbitrary pool bytes that no attention path can read, so
    equality is defined up to them."""
    def walk(node):
        if isinstance(node, dict) and {"k", "v", "positions"} <= set(node):
            ok = (node["positions"] >= 0)[..., None, None]
            return {**node, "k": jnp.where(ok, node["k"], 0),
                    "v": jnp.where(ok, node["v"], 0)}
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node
    return walk(tree)


@pytest.mark.parametrize("family", ["dense", "ssm", "hybrid"])
def test_paged_admit_roundtrip_identity(family):
    """Paged twin of the roundtrip: admitting through page scatter then
    gathering the view back must reproduce the contiguous admission
    bit-exactly (up to unreadable K/V under empty position slots), with
    neighbor slots blank; freeing returns every page."""
    engc = get_engine(family)
    engp = get_engine(family, "paged")
    prompt = np.asarray([5, 9, 2, 11, 4], np.int32)
    slot = 1
    sc, fc, lc = engc.prefill_into_slot(engc.blank_state(), prompt, slot)
    sp, fp, lp = engp.prefill_into_slot(engp.blank_state(), prompt, slot)
    assert (fc, lc) == (fp, lp)
    axes = engc.slot_axes         # axes of the *contiguous view* structure
    view = cache_ops.gather_state(
        {k: v for k, v in sp.items() if k != "block_table"},
        sp["block_table"], engp.pspec)
    view, sc = _scrub_invalid_kv(view), _scrub_invalid_kv(sc)
    for s in range(BATCH):
        _assert_trees_equal(_rows(view, axes, s), _rows(sc, axes, s),
                            f"{family}: paged view slot {s} != contiguous")
    sp = engp.free_slot(sp, slot)
    assert eng_pool_restored(engp)
    assert int(sp["block_table"][slot].max()) == -1


def eng_pool_restored(eng) -> bool:
    return (eng.allocator.n_free == eng.pool_pages
            and eng.allocator.n_used == 0
            and all(not ps for ps in eng._slot_pages))


# ---------------------------------------------------------------------------
# BlockAllocator unit tests
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [1, 3])
def test_paged_step_rows_match_the_view_path(block):
    """A decode step on the pools in place reads each layer's view as the
    view path does (``L.paged_view`` against ``cache_ops.gather_pages``),
    and its writes — each layer's new rows (``L.paged_update``), then the
    rejected ones written empty (``cache_ops.commit`` on a paged dict) —
    leave K, V and positions bitwise where the view path leaves them:
    gather each slot's pages, ``cache_update``, commit, scatter back. Slot
    2 runs past its mapped pages, whose rows both paths drop; ``block`` 1
    is a step with no drafter, which rejects nothing."""
    rng = np.random.default_rng(5)
    S, B, nb, page, KV, hd = 2, 3, 4, 4, 2, 8       # S layers stacked
    NP = B * nb + 1
    table = rng.permutation(NP)[:B * nb].reshape(B, nb)
    table[2, 3] = -1
    pos0 = np.array([5, 9, 14], np.int32)           # each slot's length
    k = rng.normal(size=(S, NP, page, KV, hd)).astype(np.float32)
    v = rng.normal(size=(S, NP, page, KV, hd)).astype(np.float32)
    pos = np.full((S, NP, page), -1, np.int32)
    for b in range(B):
        for r in range(pos0[b]):
            if table[b, r // page] >= 0:
                pos[:, table[b, r // page], r % page] = r
    k_new = rng.normal(size=(S, B, block, KV, hd)).astype(np.float32)
    v_new = rng.normal(size=(S, B, block, KV, hd)).astype(np.float32)
    accept = np.array([0, block - 1, block // 2], np.int32)
    commit_pos = jnp.asarray(pos0 + accept)
    tags = {"k": cache_ops.PAGED_KV, "v": cache_ops.PAGED_KV,
            "positions": cache_ops.PAGED_POS}
    tb = jnp.asarray(table)

    pools = {"k": jnp.asarray(k), "v": jnp.asarray(v),
             "positions": jnp.asarray(pos)}
    view = {n: cache_ops.gather_pages(a, tb, tags[n])
            for n, a in pools.items()}
    view["ring"] = jnp.zeros((S,), bool)
    view = jax.vmap(L.cache_update, in_axes=(0, 0, 0, None))(
        view, jnp.asarray(k_new), jnp.asarray(v_new), jnp.asarray(pos0))
    view = cache_ops.commit({"blocks": view}, None, commit_pos,
                            jnp.asarray(accept))["blocks"]
    want = {n: cache_ops.scatter_pages(a, view[n], tb, tags[n])
            for n, a in pools.items()}

    paged = {**pools, "ring": jnp.zeros((S,), bool), "block_table": tb}
    for s in range(S):
        got_view = L.paged_view({**paged, "layer": s}, jnp.float32)
        for n, a in zip(("k", "v", "positions"), got_view):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(cache_ops.gather_pages(
                    pools[n][s], tb, tags[n])), err_msg=f"{n} view")
        paged = L.paged_update({**paged, "layer": s}, jnp.asarray(k_new[s]),
                               jnp.asarray(v_new[s]), jnp.asarray(pos0))
    paged = {n: a for n, a in paged.items() if n != "layer"}
    paged["block_table"] = jnp.broadcast_to(tb, (S,) + tb.shape)
    got = cache_ops.commit({"blocks": paged}, None, commit_pos,
                           jnp.asarray(accept), block=block)["blocks"]
    for n in tags:
        np.testing.assert_array_equal(np.asarray(got[n]),
                                      np.asarray(want[n]), err_msg=n)
    assert int((np.asarray(got["positions"]) >= 0).sum()) > int(
        (pos >= 0).sum())                  # rows were written, not dropped


def test_allocator_alloc_free_cycle():
    a = cache_ops.BlockAllocator(8)
    p1 = a.alloc(3)
    p2 = a.alloc(5)
    assert sorted(p1 + p2) == list(range(8)) and a.n_free == 0
    assert a.alloc(1) is None          # exhausted: caller waits, no raise
    a.free(p1)
    assert a.n_free == 3
    p3 = a.alloc(2)
    assert set(p3) <= set(p1)
    a.free(p2)
    a.free(p3)
    assert a.n_free == 8 and a.n_used == 0


def test_allocator_rejects_double_free_and_foreign():
    a = cache_ops.BlockAllocator(4)
    p = a.alloc(2)
    a.free(p)
    with pytest.raises(ValueError):
        a.free(p)                      # double free
    with pytest.raises(ValueError):
        a.free([99])                   # never allocated


@settings(max_examples=10, deadline=None)
@given(n_pages=st.integers(1, 32), seed=st.integers(0, 2**31 - 1))
def test_allocator_never_leaks_or_aliases(n_pages, seed):
    rng = np.random.default_rng(seed)
    a = cache_ops.BlockAllocator(n_pages)
    live = []
    for _ in range(50):
        if live and rng.random() < 0.4:
            a.free(live.pop(int(rng.integers(len(live)))))
        else:
            got = a.alloc(int(rng.integers(0, n_pages + 1)))
            if got is not None:
                live.append(got)
        flat = [p for ps in live for p in ps]
        assert len(flat) == len(set(flat)), "aliased pages"
        assert len(flat) + a.n_free == n_pages, "leaked pages"
    for ps in live:
        a.free(ps)
    assert a.n_free == n_pages


def test_allocator_refcount_semantics():
    """Refcounted frees: a page returns to the free list only when every
    holder has released it — the sharing substrate of the prefix cache."""
    a = cache_ops.BlockAllocator(4)
    p = a.alloc(1)[0]
    assert a.refcount(p) == 1
    a.incref([p])
    a.incref([p])
    assert a.refcount(p) == 3
    a.free([p])
    a.free([p])
    assert a.refcount(p) == 1 and a.n_free == 3   # still held
    a.free([p])
    assert a.refcount(p) == 0 and a.n_free == 4   # now recycled
    with pytest.raises(ValueError):
        a.free([p])                    # past zero == double free
    with pytest.raises(ValueError):
        a.incref([p])                  # can't revive a freed page
    with pytest.raises(ValueError):
        a.incref([99])                 # never allocated


def test_allocator_reset_stats():
    a = cache_ops.BlockAllocator(8)
    p = a.alloc(6)
    assert a.peak_used == 6
    a.free(p[2:])
    assert a.peak_used == 6            # peak is sticky ...
    a.reset_stats()
    assert a.peak_used == 2            # ... until reset re-bases it to now
    a.alloc(3)
    assert a.peak_used == 5


@settings(max_examples=10, deadline=None)
@given(n_pages=st.integers(1, 16), seed=st.integers(0, 2**31 - 1))
def test_allocator_refcounts_never_leak_or_alias(n_pages, seed):
    """Random alloc/incref/decref churn against a host-side model: the
    allocator's refcounts track the model exactly, distinct live pages plus
    the free list always cover the pool, and nothing is ever handed out
    twice while held."""
    rng = np.random.default_rng(seed)
    a = cache_ops.BlockAllocator(n_pages)
    refs: dict = {}                    # page -> expected refcount
    for _ in range(80):
        r = rng.random()
        if refs and r < 0.35:          # decref a random holder
            p = int(rng.choice(list(refs)))
            a.free([p])
            refs[p] -= 1
            if refs[p] == 0:
                del refs[p]
        elif refs and r < 0.55:        # share a random live page
            p = int(rng.choice(list(refs)))
            a.incref([p])
            refs[p] += 1
        else:
            got = a.alloc(int(rng.integers(0, n_pages + 1)))
            if got is not None:
                assert not set(got) & set(refs), "aliased a held page"
                for p in got:
                    refs[p] = 1
        assert a.n_used == len(refs), "live-page count drifted"
        assert a.n_used + a.n_free == n_pages, "leaked pages"
        for p, want in refs.items():
            assert a.refcount(p) == want
    for p, want in list(refs.items()):
        a.free([p] * want)
    assert a.n_free == n_pages and a.n_used == 0


def test_recycled_page_reads_empty():
    """Blank-on-alloc pin: pages recycled through free/alloc — including the
    decode-time growth path, which scatters nothing into the new page — must
    gather as empty (positions -1), not as the previous tenant's stale KV.
    (Blanking at free time is no longer possible: under refcounted sharing a
    freed slot's pages may still be mapped by the prefix cache.)"""
    eng = fresh_engine("dense", kv_layout="paged", kv_growth="incremental")
    rng = np.random.default_rng(0)
    state = eng.blank_state()
    # tenant A dirties every pool page it can: long prompt, then freed
    long = rng.integers(1, eng.tcfg.vocab_size - 2, size=16).astype(np.int32)
    state, _, _ = eng.prefill_into_slot(state, long, 0)
    state = eng.free_slot(state, 0)
    # tenant B: short prompt, then pure growth over recycled pages
    short = np.asarray([3, 1, 4], np.int32)
    state, _, last = eng.prefill_into_slot(state, short, 0)
    state, ok = eng.ensure_capacity(state, 0, 24)   # 3 pages, 2 recycled
    assert ok
    view = cache_ops.gather_state(
        {k: v for k, v in state.items() if k != "block_table"},
        state["block_table"], eng.pspec)

    # any surviving entry from tenant A would carry a position in
    # (last, 16) — stale history the attention mask would treat as valid
    def check(node):
        if isinstance(node, dict) and "positions" in node:
            pos = np.asarray(node["positions"])
            valid = pos[pos >= 0]
            assert valid.size, "tenant B's own entries missing"
            assert valid.max() <= last, \
                f"recycled page leaked stale positions: {np.unique(valid)}"
        elif isinstance(node, dict):
            for v in node.values():
                check(v)
    check({k: v for k, v in view.items() if k in ("tcache", "dcache")})


def test_no_page_leak_after_eos_and_rollback():
    """A full paged serve — speculative rollback-invalidation every
    iteration, EOS mid-stream retiring slots — must return every page."""
    eng = get_engine("dense", "paged")
    prompts = [np.asarray([5, 6, 7, 8, 9][:n], np.int32)
               for n in (3, 4, 5, 2, 5)]
    ref = Scheduler(eng).serve([Request(p, max_new_tokens=6)
                                for p in prompts])
    eos = int(ref["results"][0]["tokens"][2])   # EOS hit mid-decode
    rep = Scheduler(eng, eos_id=eos).serve([Request(p, max_new_tokens=6)
                                            for p in prompts])
    assert rep["n_requests"] == len(prompts)
    assert eng_pool_restored(eng)


def test_pool_smaller_than_slots_serializes_admission():
    """With a pool that fits only one request, admissions serialize through
    the free list but every request still completes with exact tokens."""
    eng = get_engine("dense", "paged")
    tight = fresh_engine("dense", kv_layout="paged", pool_pages=3)
    prompts = [np.asarray([3, 4, 5], np.int32),
               np.asarray([7, 8, 9, 10], np.int32)]
    rep_ref = Scheduler(eng).serve([Request(p, max_new_tokens=4)
                                    for p in prompts])
    rep = Scheduler(tight).serve([Request(p, max_new_tokens=4)
                                  for p in prompts])
    for a, b in zip(rep_ref["results"], rep["results"]):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert tight.allocator.n_free == 3


# ---------------------------------------------------------------------------
# bucketed-prefill retrace bound
# ---------------------------------------------------------------------------

def _admit_lengths(eng, lengths):
    rng = np.random.default_rng(0)
    for n in lengths:
        state = eng.blank_state()
        prompt = rng.integers(1, eng.tcfg.vocab_size - 2,
                              size=int(n)).astype(np.int32)
        eng.prefill_into_slot(state, prompt, 0)
        if eng.paged:
            eng.free_slot(state, 0)


def test_prefill_retrace_bound_padded():
    """N distinct prompt lengths compile at most ceil(log2(max_len)) padded
    prefill traces (the jit cache-size counter is the compile count)."""
    eng = fresh_engine("dense")
    max_len = eng.ecfg.max_len
    bound = int(np.ceil(np.log2(max_len)))
    lengths = list(range(1, 13))       # 12 distinct lengths > bound
    assert len(lengths) > bound
    _admit_lengths(eng, lengths)
    assert eng._prefill_pad._cache_size() <= bound
    assert eng._prefill._cache_size() == 0     # exact-length path never used


def test_prefill_retrace_bound_chunked():
    """Recurrent families chunk instead of pad: prefill traces are bounded
    by the distinct leading buckets, chunk traces by the distinct trailing
    ones — both within ceil(log2(max_len))."""
    eng = fresh_engine("ssm")
    bound = int(np.ceil(np.log2(eng.ecfg.max_len)))
    _admit_lengths(eng, list(range(1, 13)))
    assert eng._prefill._cache_size() <= bound
    assert eng._chunk._cache_size() <= bound
    assert eng._prefill_pad._cache_size() == 0


def test_incremental_growth_retrace_bound():
    """Decode-time ``ensure_capacity`` must not add jit traces per page
    count: the block-table row update is ONE trace for every (slot, page
    count) combination — slot index and the full-width row are both traced
    — and the paged step itself never retraces. A workload whose slots
    cross page boundaries at many distinct counts pins the bound."""
    eng = fresh_engine("dense", kv_layout="paged")
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, eng.tcfg.vocab_size - 2,
                            size=int(n)).astype(np.int32)
               for n in (3, 5, 7, 4, 6, 2)]
    budgets = [8, 6, 4, 8, 5, 7]
    rep = Scheduler(eng).serve([Request(p, max_new_tokens=b)
                                for p, b in zip(prompts, budgets)])
    assert rep["n_requests"] == len(prompts)
    # exactly one growth trace — and at least one (the workload really did
    # cross page boundaries; 0 would mean the bound wasn't exercised)
    assert eng._set_table_row._cache_size() == 1
    # the step is a {greedy_only: trace} twin pair; an all-greedy workload
    # must compile only the greedy-only twin — one trace total
    assert sum(f._cache_size() for f in eng._paged_step.values()) <= 1
    assert eng_pool_restored(eng)
    # upfront growth never touches the growth path at all
    up = fresh_engine("dense", kv_layout="paged", kv_growth="upfront")
    Scheduler(up).serve([Request(p, max_new_tokens=b)
                         for p, b in zip(prompts, budgets)])
    assert up._set_table_row._cache_size() == 0


def test_prefill_buckets_decomposition():
    assert Engine.prefill_buckets(1) == [1]
    assert Engine.prefill_buckets(8) == [8]
    assert Engine.prefill_buckets(7) == [4, 2, 1]
    assert Engine.prefill_buckets(13) == [8, 4, 1]
    for n in range(1, 200):
        bs = Engine.prefill_buckets(n)
        assert sum(bs) == n and bs == sorted(bs, reverse=True)
        assert all(b & (b - 1) == 0 for b in bs)
