"""Training runner: drafter training through the program's ``Trainer``.

The timed path is ``training/trainer.Trainer.train_batch`` over
``data/pipeline.MTPPipeline`` batches of a seeded ``markov_corpus``: the
frozen target's taps, the drafter's MTP forward and loss, the gradients and
the AdamW update, in one jitted step. The benchmark makes the target's and
the drafter's weights from the seed (``bench/weights.py``) and puts them in
the one trainer it builds. Set-up drives that trainer through its first
``check_steps`` steps on distinct rows (the first compiles) and keeps what
the check compares: each step's loss, the first gradient as the optimizer
holds it after step 1 (its first moment over 1 - b1), and each leaf's
change after the last of them. The same trainer then runs the window.

After the window the trainer is freed and the plain reference
(``bench/refs/drafter.py``) repeats those steps from the same weights on
the same batches, in float32 at the highest matmul precision.
"""
from __future__ import annotations

import gc
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from bench import flops as F
from bench import harness as H

TRACE_DIR = "bench_out/trace"


@dataclass
class TrainRun:
    cell: object
    config: dict
    mix: dict
    setup_s: float
    window: tuple
    steps: int                     # completed inside the window
    losses: List[float]
    device: dict
    peak: object
    drafter: dict
    attended: int                  # mean (query, key) pairs per sequence
    valid: int                     # mean valid expanded positions
    checks: list = field(default_factory=list)
    trace: Optional[object] = None
    trace_window: Optional[tuple] = None
    attempted: int = 0
    failed: int = 0
    control_checks: Optional[list] = None   # bench/control.py only

    def tokens_per_step(self) -> int:
        return self.mix["batch"] * self.mix["seq"]

    def step_flops(self) -> float:
        """Target forward (no LM head: training reads only its taps) and
        drafter forward and backward (3x forward) per step."""
        B, S = self.mix["batch"], self.mix["seq"]
        tgt = F.target_flops(self.config, B * S,
                             B * F.prefill_attended(S), 0)
        drf = F.drafter_flops(self.drafter, B * self.valid,
                              B * self.attended, B * self.valid)
        return tgt + 3.0 * drf


def mask_pairs(pos: np.ndarray, depth: np.ndarray) -> int:
    """(query, key) pairs the MTP attention mask admits in one sequence."""
    anchor = pos - depth
    ok = depth >= 0
    see = (((depth[None] == 0) & (pos[None] <= anchor[:, None]))
           | ((anchor[None] == anchor[:, None])
              & (depth[None] <= depth[:, None])))
    return int((see & ok[:, None] & ok[None]).sum())


def leaf_norms(tree) -> List[float]:
    import jax
    import jax.numpy as jnp
    return [float(x) for x in jax.jit(lambda t: [
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
        for a in jax.tree.leaves(t)])(tree)]


def change_norms(now, key, abstract) -> List[float]:
    """Per-leaf norm of ``now`` minus the initial weights, made again from
    ``key`` inside the same program."""
    import jax
    import jax.numpy as jnp
    from bench import weights

    @jax.jit
    def diff(now, key):
        start = weights.make(abstract, key)
        return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                            - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(now), jax.tree.leaves(start))]

    return [float(x) for x in diff(now, key)]


def worst_leaf(prog: List[float], ref: List[float], keep: List[bool]) -> float:
    """Largest |program norm - reference norm| over the kept leaves, each
    against the larger of its reference norm and the median kept one."""
    kept = [(p, r) for p, r, k in zip(prog, ref, keep) if k]
    med = float(np.median([r for _, r in kept]))
    return max(abs(p - r) / max(r, med) for p, r in kept)


def run(cell, seed: int, seconds: float, traced: bool, t_start: float,
        counter, devices, control: bool = False) -> TrainRun:
    """One run of a training cell. ``control`` (``bench/control.py`` only,
    never a benchmark run) also repeats the checked steps with the
    reference in bfloat16 and keeps the checks its readings make, under
    the cell's limits, in ``control_checks``."""
    import jax
    from repro.configs import DrafterConfig, get_config
    from repro.core import drafter as D
    from repro.data import MTPPipeline, markov_corpus
    from repro.models import get_model
    from repro.optim import adamw_init
    from repro.training import TrainConfig, Trainer

    from bench import weights
    from bench.peaks import peak_for
    from bench.serve import check_config

    cfg, mix = cell.config, cell.mix
    peak = peak_for(devices[0].device_kind)
    tcfg = get_config(cfg["program_arch"])
    check_config(cfg, tcfg)
    dshape = dict(F.drafter_shape(cfg, mix["drafter"]), **mix["drafter"])
    dcfg = DrafterConfig(n_layers=mix["drafter"]["layers"],
                         k_train=mix["k_train"], cod_rate=mix["cod_rate"]
                         ).resolve(tcfg)
    mine = {"num_attention_heads": dcfg.n_heads,
            "num_key_value_heads": dcfg.n_kv_heads,
            "head_dim": dcfg.head_dim, "intermediate_size": dcfg.d_ff,
            "rope_theta": dcfg.rope_theta, "rms_norm_eps": dcfg.norm_eps}
    bad = {k: (dshape[k], v) for k, v in mine.items() if dshape[k] != v}
    if bad or dcfg.hidden_state_variant != "shared":
        raise ValueError(f"the program's drafter differs from the mix: {bad}")

    key = jax.random.PRNGKey(H.seed32(seed))
    model = get_model(tcfg)
    tparams = weights.make(jax.eval_shape(model.init, key),
                           jax.random.fold_in(key, 1))
    dabs = jax.eval_shape(lambda k: D.init_params(dcfg, tcfg, k), key)
    dkey = jax.random.fold_in(key, 2)
    opt = mix["optimizer"]
    tc = TrainConfig(lr=opt["lr"], total_steps=opt["total_steps"],
                     warmup_ratio=opt["warmup_ratio"],
                     weight_decay=opt["weight_decay"],
                     max_grad_norm=opt["max_grad_norm"])
    tr = Trainer(tcfg, dcfg, tparams, tc, seed=H.seed32(seed, 4))
    tr.dparams = tr.opt_state = None           # the trainer's own: freed,
    gc.collect()                               # then the benchmark's
    tr.dparams = weights.make(dabs, dkey)
    tr.opt_state = adamw_init(tr.dparams)
    corpus = markov_corpus(H.seed32(seed, 5), mix["corpus_seqs"], mix["seq"],
                           cfg["vocab_size"])
    pipe = MTPPipeline(corpus, k_train=mix["k_train"],
                       cod_rate=mix["cod_rate"], batch=mix["batch"],
                       seed=H.seed32(seed, 6), segments=1)

    def batches():
        while True:
            yield from pipe

    feed = batches()
    first, losses, g1 = [], [], None
    for i in range(mix["check_steps"]):
        b = next(feed)
        first.append(b)
        losses.append(tr.train_batch(b)["loss"])
        if i == 0:
            g1 = [n / (1 - opt["b1"]) for n in leaf_norms(tr.opt_state.m)]
    d_prog = change_norms(tr.dparams, dkey, dabs)
    setup_s = H.now() - t_start
    H.log(f"set-up {setup_s:.3f} s ({counter.count} programs lowered); "
          f"losses {losses}")

    if traced:
        import shutil
        shutil.rmtree(H.ROOT / TRACE_DIR, ignore_errors=True)
    window_losses = []
    trace_window = None
    counter.mark()
    w0 = H.now()
    steps = 0
    t_trace = w0 + mix["trace_offset_s"] if traced else None
    span = None
    while True:
        now = H.now()
        if t_trace is not None and now >= t_trace and span is None:
            jax.profiler.start_trace(str(H.ROOT / TRACE_DIR))
            span = jax.profiler.TraceAnnotation("bench.window")
            span.__enter__()
            ts0 = H.now()
        if span is not None and trace_window is None \
                and now >= ts0 + mix["trace_s"]:
            trace_window = (ts0, now)
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        b = next(feed)
        with jax.profiler.TraceAnnotation("bench.train_batch"):
            m = tr.train_batch(b)
        if H.now() > w0 + seconds:
            break
        steps += 1
        window_losses.append(m["loss"])
    w1 = w0 + seconds
    H.log(f"programs lowered inside the window: {counter.since_mark()}")
    if span is not None and trace_window is None:
        trace_window = (ts0, H.now())
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    device = H.device_info(devices, cell.chips)
    del tr, feed
    gc.collect()

    pairs = [mask_pairs(p, d) for b in first for p, d in zip(b.pos, b.depth)]
    valid = [int((d >= 0).sum()) for b in first for d in b.depth]
    r = TrainRun(cell=cell, config=cfg, mix=mix, setup_s=setup_s,
                 window=(w0, w1), steps=steps, losses=window_losses,
                 device=device, peak=peak, drafter=dshape,
                 attended=int(np.mean(pairs)), valid=int(np.mean(valid)),
                 trace_window=trace_window)
    r.attempted = steps
    r.failed = int(sum(1 for x in window_losses if not np.isfinite(x)))
    if traced:
        from bench import trace as TR
        events = TR.events_from_xplane(TR.find_xplane(str(H.ROOT / TRACE_DIR)))
        r.trace = TR.reduce(events, window_span="bench.window")
        H.log("traced programs (device s, calls): " + ", ".join(
            f"{k} {v[0]:.4f} {v[1]}" for k, v in sorted(
                r.trace.programs.items(), key=lambda kv: -kv[1][0])[:8]))
    r.checks = check(r, tparams, dabs, dkey, first, losses, g1, d_prog,
                     control)
    return r


def reference_steps(r: TrainRun, tparams, dabs, dkey, batches, dtype):
    """The plain reference's first steps from the benchmark's initial
    weights: (each step's loss, the first clipped gradient's leaf norms,
    each leaf's change after the last step). ``dtype`` float32 is the
    reference, at the highest matmul precision; bfloat16 is its control,
    with parameters, moments and activations held in bfloat16."""
    import jax
    import jax.numpy as jnp
    from bench import weights
    from bench.correct import load_reference

    cfg, mix = r.config, r.mix
    tref = load_reference(cfg["reference"])
    dref = load_reference(mix["reference"])
    opt = mix["optimizer"]
    vocab = cfg["vocab_size"]
    d = r.drafter

    def step(p, m, v, tparams, batch, i):
        taps = jax.vmap(lambda t: dref.target_taps(tref, cfg, tparams, t,
                                                   dtype))(batch[0])
        lval, g = jax.value_and_grad(
            lambda p: dref.loss(d, p, taps, batch, vocab))(p)
        p, m, v, g = dref.adamw(p, m, v, g, i, opt)
        cast = lambda t: jax.tree.map(lambda x: x.astype(dtype), t)
        return cast(p), cast(m), cast(v), lval, [
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(g)]

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    t0 = H.now()
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        p = jax.tree.map(lambda x: x.astype(dtype), weights.make(dabs, dkey))
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        ref_losses, g_ref = [], None
        for i, b in enumerate(batches, start=1):
            batch = tuple(jnp.asarray(x) for x in (b.tokens, b.pos, b.depth,
                                                   b.labels))
            p, m, v, lval, gn = step(p, m, v, tparams, batch, i)
            ref_losses.append(float(lval))
            if i == 1:
                g_ref = [float(x) for x in gn]
        del m, v
        d_ref = change_norms(p, dkey, dabs)
    H.log(f"reference ({jnp.dtype(dtype).name}) over {len(batches)} steps "
          f"in {H.now() - t0:.3f} s; losses {ref_losses}")
    return ref_losses, g_ref, d_ref


def readings(ref, losses, g1, d_prog) -> dict:
    """The three numbers read, of one run against the reference. Only
    ``loss_gap`` and ``change_gap`` are compared: the first gradient's
    ``grad_gap`` reads alike (near 1e-3) for sound runs and the bfloat16
    control, because the float32 drafter's matmuls already run at the
    chip's default (bfloat16-pass) precision, so no limit could separate
    them; it is logged, not compared."""
    ref_losses, g_ref, d_ref = ref
    med = float(np.median(g_ref))
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: left out by this rule, never by name
    keep = [x >= 1e-3 * med for x in g_ref]
    return {"loss_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(losses, ref_losses)),
            "grad_gap": worst_leaf(g1, g_ref, keep),
            "change_gap": worst_leaf(d_prog, d_ref, keep)}


def check(r: TrainRun, tparams, dabs, dkey, batches, losses, g1, d_prog,
          control: bool = False):
    """The reference repeats the first steps; compare each step's loss,
    the first gradient's leaf norms and each leaf's change after them."""
    import jax.numpy as jnp
    ref = reference_steps(r, tparams, dabs, dkey, batches, jnp.float32)
    got = readings(ref, losses, g1, d_prog)
    limits = r.cell.check["limits"]
    if control:
        ctl_losses, g_ctl, d_ctl = reference_steps(r, tparams, dabs, dkey,
                                                   batches, jnp.bfloat16)
        ctl = readings(ref, ctl_losses, g_ctl, d_ctl)
        r.control_checks = [H.Check(k, v, limits[k]) for k, v in ctl.items()
                            if k in limits]
    H.log(f"grad_gap (read, not compared): {got['grad_gap']!r}")
    return [H.Check(k, v, limits[k]) for k, v in got.items() if k in limits]

