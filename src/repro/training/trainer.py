"""Drafter training: the paper's scalable MTP training loop.

One jitted ``train_step`` covers both regimes:
- whole-sequence MTP training (train_4k dry-run shape), and
- *segmented* training (paper §3.2): the pipeline emits Algorithm-1 segments;
  ``segment_grads`` runs one forward/backward per segment and the
  GradAccumulator sums them into a single optimizer step. Because each query
  appears in exactly one segment with its full attention context, the summed
  gradient equals the unpartitioned gradient (tested in
  tests/test_partition.py::test_segmented_grads_match).

The AR EAGLE-3 baseline trains through ``losses.ttt_forward_loss``
(training-time-test unroll + optional HCA).
"""
from __future__ import annotations

import functools
import time
import weakref
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DrafterConfig, ModelConfig
from repro.core import drafter as D
from repro.core import losses
from repro.data.pipeline import MTPBatch, MTPPipeline
from repro.models import get_model
from repro.optim import (GradAccumulator, adamw_init, adamw_update,
                         apply_updates, linear_warmup_schedule)
from repro.tracing import GcSpans, span


@dataclass
class TrainConfig:
    lr: float = 1e-4                  # paper §5.1
    total_steps: int = 1000
    warmup_ratio: float = 0.0025
    weight_decay: float = 0.01
    max_grad_norm: float = 1.0
    depth_weight_decay: float = 1.0
    hca_weight: float = 0.1


def make_train_step(tcfg: ModelConfig, dcfg: DrafterConfig,
                    tc: TrainConfig) -> Callable:
    """Whole-batch drafter train step (also the dry-run's train_step)."""
    model = get_model(tcfg)
    sched = linear_warmup_schedule(tc.lr, tc.total_steps, tc.warmup_ratio)

    def step(tparams, dparams, opt_state, tokens, pos, depth, labels, rng,
             **extras):
        # Each layer is a named sub-program, a nested jit that XLA inlines:
        # its ops' op_name reads jit(step)/jit(taps|drafter|update)/... The
        # names are part of the step's lowered structure, which the
        # persistent compilation cache keys (op_name metadata alone is
        # not), so a step compiled before they existed is never handed
        # back in place of this one.
        @jax.jit
        def taps(tparams, tokens, extras):
            tout = model.forward(tparams, tokens, mode="train",
                                 collect_taps=True, **extras)
            t = jax.lax.stop_gradient(tout.taps)
            # VLM early fusion: taps cover [vision, text]; drafter
            # positions index the text region.
            if tcfg.family == "vlm" and t.shape[1] != tokens.shape[1]:
                t = t[:, -tokens.shape[1]:]
            return t

        @jax.jit
        def drafter(dparams, tokens, taps, pos, depth, labels, rng):
            """The drafter's forward, loss and backward."""
            def loss_fn(dp):
                if dcfg.parallel:
                    logits, hidden = D.mtp_forward(dcfg, tcfg, dp, tokens,
                                                   taps, pos, depth, rng=rng)
                    loss, metrics = losses.mtp_loss(
                        logits, labels, depth,
                        depth_weight_decay=tc.depth_weight_decay)
                else:
                    loss, metrics = losses.ttt_forward_loss(
                        dcfg, tcfg, dp, tokens, taps,
                        hca_weight=tc.hca_weight)
                return loss, metrics

            return jax.value_and_grad(loss_fn, has_aux=True)(dparams)

        @jax.jit
        def update(grads, opt_state, dparams):
            updates, opt_state, om = adamw_update(
                grads, opt_state, dparams, lr=sched,
                weight_decay=tc.weight_decay,
                max_grad_norm=tc.max_grad_norm)
            return apply_updates(dparams, updates), opt_state, om

        (loss, metrics), grads = drafter(
            dparams, tokens, taps(tparams, tokens, extras), pos, depth,
            labels, rng)
        dparams, opt_state, om = update(grads, opt_state, dparams)
        metrics.update(om)
        return dparams, opt_state, metrics

    # dparams and opt_state are replaced every step: donating them lets the
    # updated trees reuse their buffers instead of holding both copies live
    return jax.jit(step, donate_argnums=(1, 2))


def make_segment_step(tcfg: ModelConfig, dcfg: DrafterConfig,
                      tc: TrainConfig):
    """(taps once per sequence) + (grads per segment) + (apply once)."""
    model = get_model(tcfg)
    sched = linear_warmup_schedule(tc.lr, tc.total_steps, tc.warmup_ratio)

    @jax.jit
    def taps_fn(tparams, tokens, **extras):
        tout = model.forward(tparams, tokens, mode="train",
                             collect_taps=True, **extras)
        taps = tout.taps
        if tcfg.family == "vlm" and taps.shape[1] != tokens.shape[1]:
            taps = taps[:, -tokens.shape[1]:]
        return jax.lax.stop_gradient(taps)

    @jax.jit
    def seg_grads(dparams, tokens, taps, pos, depth, labels, rng):
        def loss_fn(dp):
            logits, _ = D.mtp_forward(dcfg, tcfg, dp, tokens, taps,
                                      pos, depth, rng=rng)
            return losses.mtp_loss(logits, labels, depth,
                                   depth_weight_decay=tc.depth_weight_decay)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(dparams)
        return grads, metrics

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def apply_fn(dparams, opt_state, grads):
        updates, opt_state, om = adamw_update(
            grads, opt_state, dparams, lr=sched,
            weight_decay=tc.weight_decay, max_grad_norm=tc.max_grad_norm)
        return apply_updates(dparams, updates), opt_state, om

    return taps_fn, seg_grads, apply_fn


class Trainer:
    """Epoch loop over an MTPPipeline; handles both whole-sequence and
    segmented (within-sequence accumulation) batches."""

    def __init__(self, tcfg: ModelConfig, dcfg: DrafterConfig,
                 tparams: dict, tc: TrainConfig, *, seed: int = 0,
                 extras: Optional[dict] = None):
        self.tcfg, self.dcfg, self.tc = tcfg, dcfg, tc
        self.tparams = tparams
        self.extras = extras or {}
        key = jax.random.PRNGKey(seed)
        self.dparams = D.init_params(dcfg, tcfg, key)
        self.opt_state = adamw_init(self.dparams)
        self.rng = jax.random.fold_in(key, 7)
        self._step = make_train_step(tcfg, dcfg, tc)
        self._taps, self._seg_grads, self._apply = make_segment_step(
            tcfg, dcfg, tc)
        self._accum = None
        self.metrics_log = []
        # each collection while the trainer lives is a host.gc span on the
        # trace; the hook goes with the trainer
        self.gc = GcSpans().install()
        weakref.finalize(self, self.gc.remove)

    def _advance_rng(self):
        # training data-order stream: draws are sequential by construction
        # and never replayed per-position, so split-and-carry is the intent
        self.rng, sub = jax.random.split(self.rng)  # repro-lint: disable=PRNG01
        return sub

    def train_batch(self, batch) -> dict:
        if isinstance(batch, MTPBatch):
            with span("train.put"):
                arrays = [jnp.asarray(x) for x in (batch.tokens, batch.pos,
                                                   batch.depth, batch.labels)]
            with span("train.step"):
                self.dparams, self.opt_state, m = self._step(
                    self.tparams, self.dparams, self.opt_state, *arrays,
                    self._advance_rng(), **self.extras)
            with span("train.readback"):
                return {k: float(v) for k, v in m.items()}
        # segmented: within-sequence gradient accumulation (paper §3.2)
        segs = batch
        if self._accum is None:
            self._accum = GradAccumulator(self.dparams)
        taps = self._taps(self.tparams, jnp.asarray(segs[0].tokens),
                          **self.extras)
        acc = self._accum.init()
        last_m = {}
        for sg in segs:
            grads, m = self._seg_grads(
                self.dparams, jnp.asarray(sg.tokens), taps,
                jnp.asarray(sg.pos), jnp.asarray(sg.depth),
                jnp.asarray(sg.labels), self._advance_rng())
            acc = GradAccumulator.add(acc, grads, float(m["valid_tokens"]))
            last_m = m
        self.dparams, self.opt_state, om = self._apply(
            self.dparams, self.opt_state, GradAccumulator.mean(acc))
        out = {k: float(v) for k, v in last_m.items()}
        out.update({k: float(v) for k, v in om.items()})
        return out

    def train(self, pipeline: MTPPipeline, epochs: int = 1,
              log_every: int = 0) -> list:
        step = 0
        for ep in range(epochs):
            for batch in pipeline:
                m = self.train_batch(batch)
                m["epoch"] = ep
                self.metrics_log.append(m)
                step += 1
                if log_every and step % log_every == 0:
                    print(f"step {step}: loss={m['loss']:.4f} "
                          f"acc={m.get('acc', 0):.3f} "
                          f"mtp_acc={m.get('mtp_acc', 0):.3f}")
        return self.metrics_log
