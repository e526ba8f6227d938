"""Per-architecture smoke tests (assignment requirement): a REDUCED variant
of each family (2 layers, d_model<=256, <=4 experts) runs one forward and
one train step on CPU with correct output shapes and no NaNs, plus
prefill+decode == full-forward consistency (cache correctness)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, DrafterConfig, get_config

# big/multi-modal reduced configs still cost 5-17 s of jit each on CPU;
# one representative per family stays in the default (fast) selection
HEAVY_ARCHS = {"llama4-maverick-400b-a17b", "whisper-base", "gemma-7b",
               "gemma2-27b", "internvl2-1b", "dbrx-132b",
               "recurrentgemma-2b"}
ARCH_PARAMS = [pytest.param(a, marks=pytest.mark.slow)
               if a in HEAVY_ARCHS else a for a in ARCH_IDS]
from repro.models import get_model, make_extras

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_config(arch).reduced()
            m = get_model(cfg)
            cache[arch] = (cfg, m, m.init(KEY))
        return cache[arch]
    return get


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_smoke_forward(arch, built):
    cfg, m, params = built(arch)
    B, S = 2, 16
    tl = m.text_len(S, "train")
    toks = jax.random.randint(KEY, (B, tl), 0, cfg.vocab_size)
    extras = make_extras(cfg, B, "train", KEY)
    out = m.forward(params, toks, mode="train", **extras)
    assert out.logits.shape == (B, S, cfg.vocab_size)
    assert out.taps.shape == (B, S, 3 * cfg.d_model)
    assert not bool(jnp.isnan(out.logits).any())
    assert not bool(jnp.isnan(out.taps).any())


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_smoke_train_step(arch, built):
    """One drafter train step on the reduced target: loss is finite and the
    drafter parameters change."""
    from repro.training import TrainConfig, make_train_step
    from repro.core import drafter as D, cod
    from repro.optim import adamw_init

    cfg, m, tparams = built(arch)
    dcfg = DrafterConfig(n_layers=1, k_train=3).resolve(cfg)
    dparams = D.init_params(dcfg, cfg, jax.random.fold_in(KEY, 1))
    opt = adamw_init(dparams)
    step = make_train_step(cfg, dcfg, TrainConfig(total_steps=10))

    B, S = 2, 16
    tl = m.text_len(S, "train")
    toks = jax.random.randint(KEY, (B, tl), 0, cfg.vocab_size)
    rng = np.random.default_rng(0)
    pos, depth = cod.sample_cod(rng, tl, 3, 0.7)
    tgt = pos + 2
    labels = np.where(tgt < tl, np.asarray(toks)[:, np.clip(tgt, 0, tl - 1)], -1)
    extras = make_extras(cfg, B, "train", KEY)
    # the step donates dparams and opt: keep a host copy to compare against
    before = [np.asarray(a) for a in jax.tree.leaves(dparams)]
    new_dp, new_opt, metrics = step(
        tparams, dparams, opt, toks, jnp.asarray(pos), jnp.asarray(depth),
        jnp.asarray(labels), KEY, **extras)
    assert np.isfinite(float(metrics["loss"]))
    moved = any(
        not np.allclose(a, np.asarray(b))
        for a, b in zip(before, jax.tree.leaves(new_dp)))
    assert moved


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_prefill_decode_consistency(arch, built):
    cfg, m, params = built(arch)
    B, S, T = 2, 12, 4
    toks = jax.random.randint(KEY, (B, S + T), 0, cfg.vocab_size)
    extras = make_extras(cfg, B, "prefill", KEY)
    full = m.forward(params, toks, mode="train", **extras)
    off = cfg.vision_tokens if cfg.family == "vlm" else 0
    cache = m.make_cache(B, off + S + T, dtype=jnp.float32)
    pre = m.forward(params, toks[:, :S], mode="prefill", cache=cache,
                    **extras)
    pos = jnp.broadcast_to(
        jnp.arange(off + S, off + S + T, dtype=jnp.int32)[None], (B, T))
    dec = m.forward(params, toks[:, S:], mode="decode", cache=pre.cache,
                    positions=pos)
    a = np.asarray(full.logits[:, off + S:off + S + T])
    b = np.asarray(dec.logits)
    np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-3)


def test_sliding_window_ring_cache_matches_local_attention():
    """Decode past the window with a ring cache must equal a full local-
    attention forward (the long_500k mechanism at test scale)."""
    cfg = get_config("qwen2-1.5b").reduced().replace(
        attn_pattern=("local",), window_size=8)
    m = get_model(cfg)
    params = m.init(KEY)
    B, S, T = 2, 20, 4
    toks = jax.random.randint(KEY, (B, S + T), 0, cfg.vocab_size)
    full = m.forward(params, toks, mode="train")
    cache = m.make_cache(B, S + T, dtype=jnp.float32)   # ring: W=8 < 24
    pre = m.forward(params, toks[:, :S], mode="prefill", cache=cache)
    pos = jnp.broadcast_to(jnp.arange(S, S + T, dtype=jnp.int32)[None],
                           (B, T))
    dec = m.forward(params, toks[:, S:], mode="decode", cache=pre.cache,
                    positions=pos)
    np.testing.assert_allclose(np.asarray(full.logits[:, S:]),
                               np.asarray(dec.logits), atol=5e-4, rtol=5e-3)
    # ring buffers really are bounded
    k_shape = jax.tree.leaves(pre.cache)[0].shape
    assert any(s == 8 for leaf in jax.tree.leaves(pre.cache)
               for s in leaf.shape)


@pytest.mark.parametrize("arch", ["gemma2-27b", "llama4-maverick-400b-a17b"])
def test_alternating_pattern_layers(arch):
    cfg = get_config(arch)
    kinds = [cfg.attn_kind(i) for i in range(4)]
    assert "local" in kinds and "global" in kinds


def test_moe_aux_losses_present():
    cfg = get_config("dbrx-132b").reduced()
    m = get_model(cfg)
    params = m.init(KEY)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    out = m.forward(params, toks, mode="train")
    assert float(out.aux["lb_loss"]) > 0.0
    assert float(out.aux["z_loss"]) > 0.0
