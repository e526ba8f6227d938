"""Smoke run of the main paths on a TPU at qwen2-1.5b full width.

    python chip_smoke.py             # one chip: train, serve, kernels
    python chip_smoke.py --chips 4   # model-sharded serve vs one-chip engine

Everything is built from ``--seed``: random weights at the published widths
(28 layers, d_model 1536, vocab 151,936), a seeded Markov corpus, seeded
prompts. Nothing is read from disk but the code.

One chip:

  train    3 whole-sequence steps of a 4-layer drafter (K_train 8, COD 0.8)
           on the frozen target, seq 256, batch 1; every loss finite.
  serve    the trained drafter behind Engine + Scheduler: B=4, paged KV
           (page 16, max_len 1024), 4 requests of 64-512 prompt tokens and
           64 new tokens, in modes none, ar and parallel at K=5, each queue
           served twice (the first pass compiles). Greedy streams of ar and
           parallel must equal none up to their first difference, and that
           difference must be a near-tie (``NEAR_TIE_ULPS``). One parallel
           request is sampled (T=0.8) and must repeat across the passes.
  kernels  the four Pallas kernels compiled for the chip (``tpu_custom_call``
           in the program) against the jnp oracles of kernels/ref.py.

Four chips: the paged parallel engine storage-sharded over a 4-device
("model",) mesh against the one-chip engine, same requests, same rule.

Set-up times, tokens, acceptance length and peak device memory are printed
as a record that the path ran, not as metrics. The last line of standard
output is ``{"ok": true, "device": {...}}``; any failure exits non-zero
without it, and so does a first JAX device that is not a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2-1.5b"
SEQ, TRAIN_STEPS = 256, 3
K, BATCH, MAX_LEN, PAGE, MAX_NEW = 5, 4, 1024, 16, 64
MODES = ("none", "ar", "parallel")
SAMPLED = 3                  # index of the sampled request in parallel mode
# A greedy stream may leave the reference stream only where the target's
# best two logits are a near-tie. The served target computes in bfloat16
# and rounds its logits to bfloat16, whose spacing at the best logit's
# magnitude is one ulp; two serving paths that reduce in different orders
# (1 query per step vs K+1, one device vs a gathered mesh) can each move a
# logit by about half an ulp at the head and by more through 28 layers of
# bfloat16 activations. A difference counts as a near-tie when both tokens
# lie within 4 such ulps of the best logit of one teacher-forced float32
# forward at "highest" matmul precision. The measured bfloat16-vs-float32
# error at each difference is printed beside it.
NEAR_TIE_ULPS = 4
KERNEL_TOL = 2e-2            # bfloat16 tolerance of tests/test_kernels.py


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def peak_gb(dev) -> float:
    return dev.memory_stats()["peak_bytes_in_use"] / 1e9


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(tcfg, tparams, seed: int, dev) -> dict:
    from repro.configs import DrafterConfig
    from repro.data import MTPPipeline, markov_corpus
    from repro.training import TrainConfig, Trainer

    dcfg = DrafterConfig(n_layers=4, k_train=8, cod_rate=0.8).resolve(tcfg)
    corpus = markov_corpus(seed, TRAIN_STEPS, SEQ, tcfg.vocab_size)
    pipe = MTPPipeline(corpus, k_train=dcfg.k_train, cod_rate=dcfg.cod_rate,
                       batch=1, seed=seed, segments=1)
    tr = Trainer(tcfg, dcfg, tparams, TrainConfig(total_steps=TRAIN_STEPS),
                 seed=seed)
    losses = []
    for i, batch in enumerate(pipe):
        t0 = time.perf_counter()
        m = tr.train_batch(batch)
        dt = time.perf_counter() - t0
        losses.append(m["loss"])
        log(f"train step {i}: loss {m['loss']!r}, {dt:.2f} s"
            + (" (set-up: compile included)" if i == 0 else ""))
    check(len(losses) == TRAIN_STEPS and bool(np.all(np.isfinite(losses))),
          f"train losses {losses}")
    log(f"train: {TRAIN_STEPS} steps at seq {SEQ} (M={batch.pos.shape[1]} "
        f"expanded positions), peak device memory {peak_gb(dev):.3f} GB")
    return tr.dparams


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_prompts(seed: int, vocab: int) -> list:
    rng = np.random.default_rng(seed)
    lens = rng.integers(64, 513, size=BATCH)
    return [rng.integers(0, vocab - 2, size=n).astype(np.int32) for n in lens]


def serve_queue(eng, prompts, policies, passes: int) -> list:
    """Serve the queue ``passes`` times; the per-pass lists of streams."""
    from repro.serving import Request, Scheduler
    sched = Scheduler(eng)
    out = []
    for p in range(passes):
        t0 = time.perf_counter()
        rep = sched.serve([Request(pr, max_new_tokens=MAX_NEW, sampling=sp)
                           for pr, sp in zip(prompts, policies)])
        dt = time.perf_counter() - t0
        streams = [r["tokens"] for r in rep["results"]]
        check(all(len(s) == MAX_NEW for s in streams),
              f"streams of lengths {[len(s) for s in streams]}")
        log(f"  pass {p}: {rep['total_new_tokens']} tokens in "
            f"{rep['iterations']} iterations, acceptance length "
            f"{rep['weighted_acceptance_length']!r}, {dt:.2f} s"
            + (" (set-up: compile included)" if p == 0 else " (warm)"))
        out.append(streams)
    return out


def first_difference(a, b):
    diff = np.flatnonzero(np.asarray(a) != np.asarray(b))
    return int(diff[0]) if diff.size else None


def near_tie_check(model, tparams, cases) -> None:
    """``cases``: (label, prompt, reference stream, other stream, j) with the
    streams first differing at new token j. Each difference must be a
    near-tie of one teacher-forced float32 forward over prompt +
    reference[:j]."""
    import jax
    import jax.numpy as jnp

    if not cases:
        return

    @jax.jit
    def logits_at(params, tokens, at):
        return model.forward(params, tokens, mode="train",
                             collect_taps=False,
                             head_positions=at).logits[:, 0]

    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), tparams)
    for label, prompt, ref, other, j in cases:
        seq = np.concatenate([prompt, ref[:j]])
        toks = np.zeros((1, MAX_LEN), np.int32)    # causal: padding is inert
        toks[0, :len(seq)] = seq
        at = jnp.asarray([len(seq) - 1], jnp.int32)
        with jax.default_matmul_precision("highest"):
            l32 = np.asarray(logits_at(p32, toks, at))[0]
        lbf = np.asarray(logits_at(tparams, toks, at))[0]
        best = float(l32.max())
        ulp = 2.0 ** (np.floor(np.log2(abs(best))) - 7)   # bfloat16 spacing
        a, b = int(ref[j]), int(other[j])
        below = (best - float(l32[a]), best - float(l32[b]))
        err = max(abs(float(lbf[t] - l32[t])) for t in (a, b))
        log(f"  {label}: first difference at new token {j}: {a} vs {b}, "
            f"{below[0]!r} / {below[1]!r} below the best float32 logit "
            f"{best!r} ({max(below) / ulp:.2f} bf16 ulps); bfloat16 "
            f"teacher-forced error {err!r}")
        check(max(below) <= NEAR_TIE_ULPS * ulp,
              f"{label}: difference at new token {j} is not a near-tie")


def compare_streams(label, ref, got, prompts, indices) -> list:
    cases = []
    for i in indices:
        j = first_difference(ref[i], got[i])
        if j is not None:
            cases.append((f"{label} stream {i}", prompts[i], ref[i], got[i],
                          j))
    where = ", ".join(f"{c[0]} at {c[4]}" for c in cases) or "none"
    log(f"{label}: {len(cases)} of {len(indices)} greedy streams differ "
        f"from the reference ({where})")
    return cases


def serve_phase(tcfg, tparams, dparams, seed: int, dev) -> list:
    """Serve the queue in every mode; the greedy streams that differ from
    mode none, as near_tie_check cases."""
    from repro.launch.build import build_engine
    from repro.serving import EngineConfig, SamplingParams

    prompts = make_prompts(seed, tcfg.vocab_size)
    log(f"serve: prompts of {[len(p) for p in prompts]} tokens, "
        f"{MAX_NEW} new tokens each")
    streams = {}
    for mode in MODES:
        policies = [SamplingParams.greedy(seed=seed + i)
                    for i in range(BATCH)]
        if mode == "parallel":
            policies[SAMPLED] = SamplingParams(temperature=0.8,
                                               seed=seed + SAMPLED)
        eng = build_engine(
            tcfg, tparams,
            EngineConfig(K=K, max_new_tokens=MAX_NEW, drafter_mode=mode,
                         max_len=MAX_LEN, kv_layout="paged", page_size=PAGE),
            BATCH, layers=4, dparams=dparams)
        log(f"serve mode={mode} K={K} B={BATCH} paged page={PAGE} "
            f"max_len={MAX_LEN}:")
        passes = serve_queue(eng, prompts, policies, passes=2)
        del eng
        for i in range(BATCH):
            check(np.array_equal(passes[0][i], passes[1][i]),
                  f"{mode} stream {i} changed between passes")
        if mode == "parallel":
            log(f"  sampled request {SAMPLED} (T=0.8): identical stream in "
                "both passes")
        log(f"  peak device memory since start {peak_gb(dev):.3f} GB")
        streams[mode] = passes[1]
    cases = compare_streams("ar", streams["none"], streams["ar"], prompts,
                            range(BATCH))
    cases += compare_streams("parallel", streams["none"],
                             streams["parallel"], prompts,
                             [i for i in range(BATCH) if i != SAMPLED])
    return cases


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def kernel_cases(seed: int) -> list:
    """(name, kernel, oracle, args) at qwen2-1.5b head widths."""
    import jax
    import jax.numpy as jnp
    from repro.core import cod
    from repro.kernels import ops, ref

    H, KV, HD, T = 12, 2, 128, K + 1
    scale = HD ** -0.5
    key = jax.random.PRNGKey(seed)
    rng = np.random.default_rng(seed)

    def rand(i, shape):
        return (0.5 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)).astype(jnp.bfloat16)

    # flash: causal self-attention over one training sequence
    flash = ((1, SEQ, H, HD), (1, SEQ, KV, HD), (1, SEQ, KV, HD))
    # mtp: the COD-expanded drafter sequence of the train phase
    M = cod.expanded_length(SEQ, 8, 0.8)
    pos, dep = cod.pad_to(*cod.sample_cod(rng, SEQ, 8, 0.8), M)
    # decode: K+1 verify queries per row against rows of different length
    lens = rng.integers(T, MAX_LEN - T, size=BATCH)
    kpos = np.where(np.arange(MAX_LEN)[None] < lens[:, None],
                    np.arange(MAX_LEN)[None], -1).astype(np.int32)
    qpos = (lens[:, None] + np.arange(T)[None]).astype(np.int32)
    # paged: the same rows through block tables over a shuffled page pool
    nb = MAX_LEN // PAGE
    perm = rng.permutation(BATCH * nb)
    table = np.full((BATCH, nb), -1, np.int32)
    pos_pool = np.full((BATCH * nb, PAGE), -1, np.int32)
    for b, n in enumerate(lens):
        used = -(-int(n) // PAGE)
        pages = perm[b * nb:b * nb + used]
        table[b, :used] = pages
        flat = np.arange(used * PAGE)
        pos_pool[pages] = np.where(flat < n, flat, -1).reshape(used, PAGE)
    pool = (BATCH * nb, PAGE, KV, HD)
    return [
        ("flash_attention",
         lambda q, k, v: ops.flash_attention(q, k, v, scale=scale,
                                             interpret=False),
         lambda q, k, v: ref.attention_reference(q, k, v, scale=scale),
         [rand(i, s) for i, s in enumerate(flash)]),
        ("mtp_attention",
         lambda q, k, v, p, d: ops.mtp_attention(q, k, v, p, d, scale=scale,
                                                 interpret=False),
         lambda q, k, v, p, d: ref.mtp_attention_reference(q, k, v, p, d,
                                                           scale=scale),
         [rand(3, (1, M, H, HD)), rand(4, (1, M, KV, HD)),
          rand(5, (1, M, KV, HD)), jnp.asarray(pos), jnp.asarray(dep)]),
        ("decode_attention",
         lambda q, k, v, kp, qp: ops.decode_attention(
             q, k, v, kp, qp, scale=scale, interpret=False),
         lambda q, k, v, kp, qp: ref.decode_reference(q, k, v, kp, qp,
                                                      scale=scale),
         [rand(6, (BATCH, T, H, HD)), rand(7, (BATCH, MAX_LEN, KV, HD)),
          rand(8, (BATCH, MAX_LEN, KV, HD)), jnp.asarray(kpos),
          jnp.asarray(qpos)]),
        ("paged_decode_attention",
         lambda q, kp, vp, pp, bt, qp: ops.paged_decode_attention(
             q, kp, vp, pp, bt, qp, scale=scale, interpret=False),
         lambda q, kp, vp, pp, bt, qp: ref.paged_decode_reference(
             q, kp, vp, pp, bt, qp, scale=scale),
         [rand(9, (BATCH, T, H, HD)), rand(10, pool), rand(11, pool),
          jnp.asarray(pos_pool), jnp.asarray(table), jnp.asarray(qpos)]),
    ]


def kernel_phase(seed: int) -> None:
    import jax
    for name, kernel, oracle, args in kernel_cases(seed):
        t0 = time.perf_counter()
        compiled = jax.jit(kernel).lower(*args).compile()
        dt = time.perf_counter() - t0
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no tpu_custom_call in the compiled program")
        got = np.asarray(compiled(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(oracle)(*args), np.float32)
        err = float(np.abs(got - want).max())
        np.testing.assert_allclose(got, want, atol=KERNEL_TOL,
                                   rtol=KERNEL_TOL, err_msg=name)
        log(f"kernel {name}: compiled for the chip in {dt:.2f} s (set-up), "
            f"shape {got.shape}, max |kernel - ref| {err!r} "
            f"(tolerance {KERNEL_TOL})")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def sharded_phase(seed: int, dev) -> None:
    from repro.launch.build import build_engine, init_target
    from repro.serving import EngineConfig, SamplingParams
    from repro.sharding.utils import serving_mesh

    tcfg, model, tparams = init_target(ARCH, seed=seed)
    prompts = make_prompts(seed, tcfg.vocab_size)
    policies = [SamplingParams.greedy(seed=seed + i) for i in range(BATCH)]
    ecfg = EngineConfig(K=K, max_new_tokens=MAX_NEW, drafter_mode="parallel",
                        max_len=MAX_LEN, kv_layout="paged", page_size=PAGE)
    eng = build_engine(tcfg, tparams, ecfg, BATCH, layers=4, seed=seed)
    dparams = eng.dparams
    log(f"one-chip engine, mode=parallel K={K} B={BATCH} paged:")
    ref = serve_queue(eng, prompts, policies, passes=1)[0]
    del eng
    mesh = serving_mesh(4)
    eng = build_engine(tcfg, tparams,
                       dataclasses.replace(ecfg, shard_model=True, mesh=mesh),
                       BATCH, layers=4, dparams=dparams)
    del dparams
    log(f"model-sharded engine over {mesh.shape['model']} devices:")
    got = serve_queue(eng, prompts, policies, passes=1)[0]
    del eng
    log(f"  peak device memory (device 0) {peak_gb(dev):.3f} GB")
    cases = compare_streams("sharded", ref, got, prompts, range(BATCH))
    near_tie_check(model, tparams, cases)


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the model-sharded serve and the one-chip "
                         "engine it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: the first JAX device is {devs[0].platform!r}, "
                 "not a TPU; nothing was run")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devs)} devices")
    dev = devs[0]
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.build import init_target, use_compile_cache
    log(f"device {dev.device_kind} x{len(devs)}; compile cache "
        f"{use_compile_cache()}")

    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(args.seed, dev)
    else:
        tcfg, model, tparams = init_target(ARCH, seed=args.seed)
        log(f"target {ARCH}: {tcfg.n_layers} layers, d_model "
            f"{tcfg.d_model}, vocab {tcfg.vocab_size}, {tcfg.dtype}")
        dparams = train_phase(tcfg, tparams, args.seed, dev)
        cases = serve_phase(tcfg, tparams, dparams, args.seed, dev)
        del dparams                 # room for the float32 reference target
        near_tie_check(model, tparams, cases)
        kernel_phase(args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s, peak device "
        f"memory {peak_gb(dev):.3f} GB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
