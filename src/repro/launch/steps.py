"""Step functions + input specs for the multi-pod dry-run and launchers.

One builder per workload shape kind:

  train   → ``build_train_step``   — frozen-target taps + P-EAGLE drafter
            fwd/bwd (COD-expanded MTP positions, K_train=8, r=0.8, the
            paper's §5.1 configuration) + AdamW, with microbatch gradient
            accumulation inside the jitted step (lax.scan).
  prefill → ``build_prefill_step`` — target prefill filling the KV cache,
            returning taps + last logits.
  decode  → ``build_serve_step``   — ONE speculative iteration (P-EAGLE
            parallel draft → target verify of K+1 tokens → acceptance →
            cache commit), via serving.engine.speculative_step.

Each builder returns (fn, make_inputs) where make_inputs(mesh) yields
(args_sds, in_shardings, out_shardings?) built from ShapeDtypeStructs — no
device allocation — with NamedShardings resolved from sharding/rules under
the mesh context.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import INPUT_SHAPES, DrafterConfig, ModelConfig
from repro.core import cod
from repro.core import drafter as D
from repro.core import losses
from repro.models import extra_input_shapes, get_model
from repro.optim import adamw_init, adamw_update, apply_updates, \
    linear_warmup_schedule
from repro.serving.engine import EngineConfig, make_decode_state, \
    speculative_step
from repro.sharding.rules import cache_specs, param_specs
from repro.sharding.utils import mesh_scope, spec_for
from repro.training.trainer import TrainConfig


def batch_spec(mesh, *trailing):
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return P(axes if axes else None, *trailing)


def _shard_tree(mesh, tree, specs):
    return jax.tree.map(lambda l, s: NamedSharding(mesh, s), tree, specs)


def resolve_drafter(tcfg: ModelConfig, n_layers: int = 4,
                    **kw) -> DrafterConfig:
    return DrafterConfig(n_layers=n_layers, **kw).resolve(tcfg)


def eval_shape_tree(fn, *a, **k):
    return jax.eval_shape(fn, *a, **k)


# ---------------------------------------------------------------------------
# long-context config adaptation (DESIGN.md §4 shape skips / variants)
# ---------------------------------------------------------------------------

def adapt_for_shape(tcfg: ModelConfig, shape_name: str) -> Optional[ModelConfig]:
    """Returns the (possibly variant) config for this shape, or None = skip."""
    if shape_name != "long_500k":
        return tcfg
    if tcfg.long_context == "skip":
        return None
    if tcfg.long_context == "sliding_window":
        # beyond-spec rolling-KV variant: every layer local, window=long_window
        return tcfg.replace(attn_pattern=("local",),
                            window_size=tcfg.long_window)
    return tcfg


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def expanded_len(n: int, K: int, r: float) -> int:
    m = cod.expanded_length(n, K, r)
    return int(math.ceil(m / 128) * 128)


def build_train_step(tcfg: ModelConfig, dcfg: DrafterConfig,
                     shape_name: str = "train_4k", *, n_micro: int = 8,
                     tc: Optional[TrainConfig] = None):
    shape = INPUT_SHAPES[shape_name]
    tc = tc or TrainConfig(total_steps=10_000)
    model = get_model(tcfg)
    sched = linear_warmup_schedule(tc.lr, tc.total_steps, tc.warmup_ratio)
    n = shape.seq_len
    GB = shape.global_batch
    M = expanded_len(n, dcfg.k_train, dcfg.cod_rate)
    mb = GB // n_micro
    extras_shapes = extra_input_shapes(tcfg, GB, "train")

    def train_step(tparams, dparams, opt_state, tokens, pos, depth, labels,
                   rng, extras):
        def micro(acc, xs):
            toks, labs, ex = xs
            tout = model.forward(tparams, toks, mode="train",
                                 collect_taps=True, **ex)
            taps = jax.lax.stop_gradient(tout.taps)
            if tcfg.family == "vlm" and taps.shape[1] != toks.shape[1]:
                taps = taps[:, -toks.shape[1]:]

            def loss_fn(dp):
                logits, _ = D.mtp_forward(dcfg, tcfg, dp, toks, taps,
                                          pos, depth, rng=rng)
                return losses.mtp_loss(logits, labs, depth)

            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(dparams)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) / n_micro,
                               acc, grads)
            return acc, loss

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             dparams)
        xs = (tokens.reshape(n_micro, mb, -1),
              labels.reshape(n_micro, mb, -1),
              {k: v.reshape((n_micro, mb) + v.shape[1:])
               for k, v in extras.items()})
        grads, per_micro_loss = jax.lax.scan(micro, zeros, xs)
        updates, opt_state, om = adamw_update(
            grads, opt_state, dparams, lr=sched,
            weight_decay=tc.weight_decay, max_grad_norm=tc.max_grad_norm)
        dparams = apply_updates(dparams, updates)
        return dparams, opt_state, per_micro_loss.mean()

    def make_inputs(mesh):
        tparams_sds = eval_shape_tree(model.init, jax.random.PRNGKey(0))
        dparams_sds = eval_shape_tree(
            lambda k: D.init_params(dcfg, tcfg, k), jax.random.PRNGKey(0))
        opt_sds = eval_shape_tree(adamw_init, dparams_sds)
        tl = model.text_len(n, "train")
        args = dict(
            tparams=tparams_sds, dparams=dparams_sds, opt_state=opt_sds,
            tokens=jax.ShapeDtypeStruct((GB, tl), jnp.int32),
            pos=jax.ShapeDtypeStruct((M,), jnp.int32),
            depth=jax.ShapeDtypeStruct((M,), jnp.int32),
            labels=jax.ShapeDtypeStruct((GB, M), jnp.int32),
            rng=jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
        extras = {k: jax.ShapeDtypeStruct(s, d)
                  for k, (s, d) in extras_shapes.items()}
        with mesh_scope(mesh):
            shardings = dict(
                tparams=_shard_tree(mesh, tparams_sds, param_specs(tparams_sds)),
                dparams=_shard_tree(mesh, dparams_sds, param_specs(dparams_sds)),
                opt_state=_shard_tree(mesh, opt_sds, param_specs(opt_sds)),
                tokens=NamedSharding(mesh, batch_spec(mesh, None)),
                pos=NamedSharding(mesh, P()),
                depth=NamedSharding(mesh, P()),
                labels=NamedSharding(mesh, batch_spec(mesh, None)),
                rng=NamedSharding(mesh, P()),
            )
            ex_sh = {k: NamedSharding(mesh, batch_spec(mesh, None, None))
                     for k in extras}
        return args, extras, shardings, ex_sh

    return train_step, make_inputs


# ---------------------------------------------------------------------------
# prefill step
# ---------------------------------------------------------------------------

def build_prefill_step(tcfg: ModelConfig, shape_name: str = "prefill_32k",
                       cache_dtype=jnp.bfloat16):
    shape = INPUT_SHAPES[shape_name]
    model = get_model(tcfg)
    GB, S = shape.global_batch, shape.seq_len
    extras_shapes = extra_input_shapes(tcfg, GB, "prefill")

    def prefill_step(tparams, tokens, cache, extras):
        out = model.forward(tparams, tokens, mode="prefill", cache=cache,
                            collect_taps=True, head_last_only=True, **extras)
        first = jnp.argmax(out.logits[:, -1], axis=-1).astype(jnp.int32)
        return out.cache, out.taps[:, -1], first

    def make_inputs(mesh):
        tparams_sds = eval_shape_tree(model.init, jax.random.PRNGKey(0))
        tl = model.text_len(S, "prefill")
        cache_sds = eval_shape_tree(
            functools.partial(model.make_cache, GB, S, dtype=cache_dtype))
        args = dict(
            tparams=tparams_sds,
            tokens=jax.ShapeDtypeStruct((GB, tl), jnp.int32),
            cache=cache_sds,
        )
        extras = {k: jax.ShapeDtypeStruct(s, d)
                  for k, (s, d) in extras_shapes.items()}
        with mesh_scope(mesh):
            shardings = dict(
                tparams=_shard_tree(mesh, tparams_sds, param_specs(tparams_sds)),
                tokens=NamedSharding(mesh, batch_spec(mesh, None)),
                cache=_shard_tree(mesh, cache_sds, cache_specs(cache_sds)),
            )
            ex_sh = {k: NamedSharding(mesh, batch_spec(mesh, None, None))
                     for k in extras}
        return args, extras, shardings, ex_sh

    return prefill_step, make_inputs


# ---------------------------------------------------------------------------
# serve (decode) step — one speculative iteration
# ---------------------------------------------------------------------------

def build_serve_step(tcfg: ModelConfig, dcfg: DrafterConfig,
                     shape_name: str, *, K: int = 5,
                     cache_dtype=jnp.bfloat16,
                     drafter_mode: str = "parallel"):
    shape = INPUT_SHAPES[shape_name]
    model = get_model(tcfg)
    GB, S = shape.global_batch, shape.seq_len
    max_len = S + 64
    ecfg = EngineConfig(K=K, max_new_tokens=1 << 30,
                        drafter_mode=drafter_mode,
                        cache_dtype="bfloat16", max_len=max_len)

    def serve_step(tparams, dparams, state):
        return speculative_step(model, tcfg, dcfg, ecfg, tparams, dparams,
                                state)

    def make_state():
        # one skeleton definition (serving/engine.py) shared with the Engine
        return make_decode_state(model, tcfg, dcfg, ecfg, GB,
                                 cache_dtype=cache_dtype,
                                 taps_dtype=jnp.bfloat16, last_fill=S)

    def make_inputs(mesh):
        tparams_sds = eval_shape_tree(model.init, jax.random.PRNGKey(0))
        dparams_sds = eval_shape_tree(
            lambda k: D.init_params(dcfg, tcfg, k, dtype=jnp.bfloat16),
            jax.random.PRNGKey(0))
        state_sds = eval_shape_tree(make_state)
        with mesh_scope(mesh):
            bsp = batch_spec(mesh)
            state_specs = {
                "tokens": spec_for((GB, max_len), bsp[0]),
                "logprobs": spec_for((GB, max_len), bsp[0]),
                "last": spec_for((GB,), bsp[0]),
                "taps_last": spec_for((GB, 3 * tcfg.d_model), bsp[0], "model"),
                "tcache": cache_specs(state_sds["tcache"]),
                "dcache": cache_specs(state_sds["dcache"]),
                "drafts": spec_for((GB, max_len, ecfg.K), bsp[0]),
                "new_count": spec_for((GB,), bsp[0]),
                "slot_iters": spec_for((GB,), bsp[0]),
                "iters": P(), "row_iters": P(), "committed": P(),
                # per-slot decoding-policy rows (serving/sampling.py)
                "sampling": {"temperature": spec_for((GB,), bsp[0]),
                             "top_k": spec_for((GB,), bsp[0]),
                             "top_p": spec_for((GB,), bsp[0]),
                             "key": spec_for((GB, 2), bsp[0])},
            }
            state_sh = {}
            for k in state_sds:
                sp = state_specs[k]
                if isinstance(sp, P):
                    state_sh[k] = NamedSharding(mesh, sp)
                else:
                    state_sh[k] = jax.tree.map(
                        lambda s: NamedSharding(mesh, s), sp)
            shardings = dict(
                tparams=_shard_tree(mesh, tparams_sds, param_specs(tparams_sds)),
                dparams=_shard_tree(mesh, dparams_sds, param_specs(dparams_sds)),
                state=state_sh,
            )
        args = dict(tparams=tparams_sds, dparams=dparams_sds, state=state_sds)
        return args, {}, shardings, {}

    return serve_step, make_inputs
