"""Compile rehearsals for one TPU v5e chip at qwen2-1.5b widths.

The TPU compiler is installed even where no chip is attached: it compiles
for a described v5e topology, and refuses what the chip would refuse
(misaligned Pallas blocks, unsupported Mosaic layouts, programs larger than
the chip's memory). Nothing runs here; ``chip_smoke.py`` runs the same
paths on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import DrafterConfig, get_config
from repro.core import drafter as D
from repro.data import MTPPipeline, markov_corpus
from repro.kernels import ops
from repro.models import get_model
from repro.optim import adamw_init
from repro.serving.engine import (Engine, EngineConfig, make_decode_state,
                                  speculative_step)
from repro.training import TrainConfig, make_train_step

V5E_HBM_BYTES = 16 * 2 ** 30
B, T, H, KV, HD = 4, 6, 12, 2, 128      # qwen2-1.5b heads; B=4, T=K+1
MAX_LEN, PAGE, SEQ = 1024, 16, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _kernel_case(name):
    bf, i32 = jnp.bfloat16, jnp.int32
    scale = HD ** -0.5
    M = 1066                          # COD-expanded positions at seq 256
    if name == "flash_attention":
        return (lambda q, k, v: ops.flash_attention(
                    q, k, v, scale=scale, interpret=False),
                [((1, SEQ, H, HD), bf), ((1, SEQ, KV, HD), bf),
                 ((1, SEQ, KV, HD), bf)])
    if name == "mtp_attention":
        return (lambda q, k, v, p, d: ops.mtp_attention(
                    q, k, v, p, d, scale=scale, interpret=False),
                [((1, M, H, HD), bf), ((1, M, KV, HD), bf),
                 ((1, M, KV, HD), bf), ((M,), i32), ((M,), i32)])
    if name == "decode_attention":
        return (lambda q, k, v, kp, qp: ops.decode_attention(
                    q, k, v, kp, qp, scale=scale, interpret=False),
                [((B, T, H, HD), bf), ((B, MAX_LEN, KV, HD), bf),
                 ((B, MAX_LEN, KV, HD), bf), ((B, MAX_LEN), i32),
                 ((B, T), i32)])
    n_pages, nb = B * MAX_LEN // PAGE, MAX_LEN // PAGE
    return (lambda q, kp, vp, pp, bt, qp: ops.paged_decode_attention(
                q, kp, vp, pp, bt, qp, scale=scale, interpret=False),
            [((B, T, H, HD), bf), ((n_pages, PAGE, KV, HD), bf),
             ((n_pages, PAGE, KV, HD), bf), ((n_pages, PAGE), i32),
             ((B, nb), i32), ((B, T), i32)])


@pytest.mark.parametrize("name", ["flash_attention", "mtp_attention",
                                  "decode_attention",
                                  "paged_decode_attention"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _full_width():
    tcfg = get_config("qwen2-1.5b")
    return tcfg, get_model(tcfg), jax.random.PRNGKey(0)


def test_greedy_speculative_step_fits_one_v5e(one_chip):
    tcfg, model, key = _full_width()
    dcfg = DrafterConfig(n_layers=4).resolve(tcfg)
    ecfg = EngineConfig(K=5, max_len=MAX_LEN)

    def step(tparams, dparams, state):
        return speculative_step(model, tcfg, dcfg, ecfg, tparams, dparams,
                                state, greedy_only=True)

    args = _on(one_chip, (
        jax.eval_shape(model.init, key),
        jax.eval_shape(lambda k: D.init_params(dcfg, tcfg, k), key),
        jax.eval_shape(lambda: make_decode_state(model, tcfg, dcfg, ecfg,
                                                 B))))
    mem = jax.jit(step).lower(*args).compile().memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


def test_paged_decode_step_never_builds_the_view_on_v5e(one_chip):
    """The benchmark's paged decode step at full width (16 slots, max_len
    1552, page 16, no drafter) takes the target's pools donated and reads
    and writes them in place: they alias the step's output, and its
    temporaries stay under a quarter of one contiguous view of the cache
    (16 slots x 1552 positions x 28 layers x K and V x 2 KV heads x 128,
    float32), so no view is ever built."""
    tcfg, model, key = _full_width()
    slots, max_len = 16, 1552
    eng = Engine(tcfg, None, jax.eval_shape(model.init, key), None,
                 EngineConfig(drafter_mode="none", max_len=max_len,
                              kv_layout="paged", page_size=PAGE), slots)
    pools, rest = eng.split_pools(jax.eval_shape(eng.blank_state))
    row = lambda dt: jax.ShapeDtypeStruct((slots,), dt)   # noqa: E731
    args = _on(one_chip, (eng.tparams, None, pools, rest, row(jnp.bool_),
                          row(jnp.int32), row(jnp.int32)))
    mem = eng._paged_step[True].lower(*args).compile().memory_analysis()
    view = (slots * max_len * tcfg.n_layers * 2 * tcfg.n_kv_heads
            * tcfg.head_dim * 4)
    assert mem.alias_size_in_bytes >= view        # K and V pools donated
    assert mem.temp_size_in_bytes < view // 4


def test_donated_train_step_fits_one_v5e(one_chip):
    tcfg, model, key = _full_width()
    dcfg = DrafterConfig(n_layers=4, k_train=8, cod_rate=0.8).resolve(tcfg)
    corpus = markov_corpus(0, 1, SEQ, tcfg.vocab_size)
    batch = next(iter(MTPPipeline(corpus, k_train=8, cod_rate=0.8,
                                  batch=1)))
    dparams = jax.eval_shape(lambda k: D.init_params(dcfg, tcfg, k), key)
    args = _on(one_chip, (
        jax.eval_shape(model.init, key), dparams,
        jax.eval_shape(adamw_init, dparams),
        *(jax.ShapeDtypeStruct(np.shape(a), jnp.int32)
          for a in (batch.tokens, batch.pos, batch.depth, batch.labels)),
        jax.eval_shape(jax.random.PRNGKey, 0)))
    step = make_train_step(tcfg, dcfg, TrainConfig(total_steps=3))
    mem = step.lower(*args).compile().memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes > 0          # dparams/opt_state donated
    assert live < V5E_HBM_BYTES
